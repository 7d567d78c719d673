"""TPC-DS q28 at its published text: six price-band value profiles of
store sales.

    select *
    from (select avg(ss_list_price) B1_LP,
                 count(ss_list_price) B1_CNT,
                 count(distinct ss_list_price) B1_CNTD
          from store_sales
          where ss_quantity between 0 and 5
            and (ss_list_price between 8 and 8+10
              or ss_coupon_amt between 459 and 459+1000
              or ss_wholesale_cost between 57 and 57+20)) B1,
         (select avg(ss_list_price) B2_LP,
                 count(ss_list_price) B2_CNT,
                 count(distinct ss_list_price) B2_CNTD
          from store_sales
          where ss_quantity between 6 and 10
            and (ss_list_price between 90 and 90+10
              or ss_coupon_amt between 2323 and 2323+1000
              or ss_wholesale_cost between 31 and 31+20)) B2,
         (... ss_quantity between 11 and 15 ... 142 ... 12214 ... 79) B3,
         (... ss_quantity between 16 and 20 ... 135 ... 6071 ... 38) B4,
         (... ss_quantity between 21 and 25 ... 122 ... 836 ... 17) B5,
         (... ss_quantity between 26 and 30 ... 154 ... 7326 ... 7) B6
    limit 100

(the template's ``[LISTPRICE.n]``, ``[COUPONAMT.n]``, ``[WHOLESALECOST.n]``
at the specification's qualification values: ``BANDS`` below, ``assumed``
in the configuration — no copy of the template is on this machine.)

As Spark plans it: six scans of the one table, each ``Filter`` into an
aggregate with ONE DISTINCT column, which ``AggUtils.
planAggregateWithOneDistinct`` makes four ``HashAggregate``s — (1) group
by ``ss_list_price``, avg and count ``Partial``; (2) the same group,
``PartialMerge``; (3) no group, avg and count ``PartialMerge`` and
``count(ss_list_price)`` ``Partial`` over the deduplicated column, in one
node; (4) ``Final`` — with a hash exchange on the price between 1 and 2
and a gather to one partition between 3 and 4 where the scan has
partitions; then five ``BroadcastNestedLoopJoin BuildRight Inner`` without
a condition, in the text's order. ``GroupedData.agg`` and
``DataFrame.cross_join`` plan exactly that. Types: ``B*_LP`` is
avg(decimal(7,2)) = decimal(11,6), HALF_UP; the counts are bigint. A band
with no row answers NULL, 0, 0.

Under this generator (``harness/datagen.py``: quantity 1-100 uniform,
wholesale cost $1.00-$99.99 uniform, list price = wholesale x 1-2.5, a
coupon a fifth of one line's sales price and so never above $50) **the
coupon arm selects nothing**; it stays in the filter and is evaluated.
Every filter is on a uniform column, so no value is drawn from the data
and a task's work does not hang on the seed.
"""

from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

import reportlib as R
from planlib import FACT, scan

TABLES = (FACT,)

#: (quantity from, to), list price from (+10), coupon amount from (+1000),
#: wholesale cost from (+20): the six derived tables, in the text's order
BANDS = (((0, 5), 8, 459, 57),
         ((6, 10), 90, 2323, 31),
         ((11, 15), 142, 12214, 79),
         ((16, 20), 135, 6071, 38),
         ((21, 25), 122, 836, 17),
         ((26, 30), 154, 7326, 7))
ARMS = (("ss_list_price", 10), ("ss_coupon_amt", 1000),
        ("ss_wholesale_cost", 20))
COLUMNS = ("ss_quantity",) + tuple(name for name, _ in ARMS)

LIST_PRICE = (7, 2)
AVG_PRICE = R.avg_type(LIST_PRICE)                      # decimal(11,6)


def build(session, tables, splits, partitions=1):
    from auron_tpu.frontend.dataframe import col, functions as F
    out = None
    for i, ((q_lo, q_hi), *froms) in enumerate(BANDS, 1):
        arms = None
        for (name, width), lo in zip(ARMS, froms):
            arm = (col(name) >= R.dec_lit(str(lo))) \
                & (col(name) <= R.dec_lit(str(lo + width)))
            arms = arm if arms is None else arms | arm
        price = col("ss_list_price")
        band = scan(session, tables, FACT, splits, partitions) \
            .select(*COLUMNS) \
            .filter((col("ss_quantity") >= q_lo)
                    & (col("ss_quantity") <= q_hi) & arms) \
            .group_by().agg(F.avg(price).alias(f"B{i}_LP"),
                            F.count(price).alias(f"B{i}_CNT"),
                            F.count(price, distinct=True)
                            .alias(f"B{i}_CNTD"))
        out = band if out is None else out.cross_join(band)
    return out.limit(100)


def _between(column, lo, hi):
    if pa.types.is_decimal(column.type):
        lo, hi = Decimal(lo), Decimal(hi)
    return pc.and_(pc.greater_equal(column, pa.scalar(lo)),
                   pc.less_equal(column, pa.scalar(hi)))


def oracle(a, float32_average=False, distinct_dtype=None):
    """The one row of 18 cells. The controls carry a precision below
    decimal(7,2): the average summed and divided in float32; the distinct
    count taken over prices rounded to ``distinct_dtype`` (float32 still
    tells two prices under $1,310.72 a cent apart, float16 does not)."""
    ss = a[FACT]
    cells = {}
    for i, ((q_lo, q_hi), *froms) in enumerate(BANDS, 1):
        arms = None
        for (name, width), lo in zip(ARMS, froms):
            arm = _between(ss[name], lo, lo + width)
            arms = arm if arms is None else pc.or_(arms, arm)
        mask = pc.and_(_between(ss["ss_quantity"], q_lo, q_hi), arms)
        prices = [p for p in ss["ss_list_price"].filter(mask).to_pylist()
                  if p is not None]
        avg = None
        if prices and float32_average:
            as_f32 = np.asarray(prices, np.float32)
            mean = as_f32.sum(dtype=np.float32) / np.float32(len(prices))
            avg = R.to_type(Decimal(repr(float(mean))), AVG_PRICE)
        elif prices:
            # sum / count, once HALF_UP at decimal(11,6)
            avg = R.to_type(sum(Decimal(p) for p in prices) / len(prices),
                            AVG_PRICE)
        distinct = set(prices) if distinct_dtype is None \
            else {distinct_dtype(p) for p in prices}
        cells[f"B{i}_LP"] = R.decimal_column([avg], AVG_PRICE)
        cells[f"B{i}_CNT"] = pa.array([len(prices)], pa.int64())
        cells[f"B{i}_CNTD"] = pa.array([len(distinct)], pa.int64())
    return pa.table(cells)
