"""q65's first aggregate answered by itself: the hundred best-selling
(store, item) pairs of the year with their revenue. NOT a TPC-DS query:
the check plan of the wide-aggregation mix. q65 and q65m return the pairs
UNDER a tenth of their store's average (sums of one or two sales), so
their answers cannot see a fault in the wide sums: a lost addend, a group
dropped when the state grows or a batch merges, a narrow accumulator. This
plan runs the same ``sa`` subtree (store_sales JOIN date_dim, one year,
grouped by (store, item)) and returns its many-addend groups: revenue =
sum(cast(ss_sales_price as double)) as ``q65.py`` sums it (a float sum:
the sort path), with the count of sales.

The pairs are ranked by that count (an integer, exact) and then by their
keys, never by the double: which hundred come back does not hang on a
sum's last bits. ``q65sam.py`` is the same over decimal money (the hash
table). ``oracle(a, carry=numpy.float32)`` is the control: the same
answer with every sum carried in that type, addend by addend."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from planlib import FACT, join_dim, oj, scan, topn

TABLES = (FACT, "date_dim")


def best_sellers(money):
    """(build, oracle) over revenue summed as ``money``: ``pa.float64()``
    or the decimal type the engine sums decimal(7,2) into."""
    as_double = pa.types.is_floating(money)

    def build(session, tables, splits, partitions=1):
        from auron_tpu.columnar.schema import DataType
        from auron_tpu.frontend.dataframe import col, functions as F
        ss = scan(session, tables, FACT, splits, partitions).select(
            "ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_sales_price")
        dd = scan(session, tables, "date_dim").filter(
            (col("d_month_seq") >= 24) & (col("d_month_seq") <= 35)) \
            .select("d_date_sk")
        price = col("ss_sales_price")
        if as_double:
            price = price.cast(DataType.FLOAT64)
        return (join_dim(ss, dd, "ss_sold_date_sk", "d_date_sk")
                .group_by("ss_store_sk", "ss_item_sk")
                .agg(F.sum(price).alias("revenue"),
                     F.count(col("ss_sales_price")).alias("sales"))
                .sort(col("sales").desc(), col("ss_store_sk").asc(),
                      col("ss_item_sk").asc())
                .limit(100))

    def oracle(a, carry=None):
        dd = a["date_dim"].filter(pc.and_(
            pc.greater_equal(a["date_dim"]["d_month_seq"], 24),
            pc.less_equal(a["date_dim"]["d_month_seq"], 35))) \
            .select(["d_date_sk"])
        ssj = oj(a[FACT], dd, ["ss_sold_date_sk"], ["d_date_sk"])
        if as_double:
            ssj = ssj.set_column(
                ssj.column_names.index("ss_sales_price"), "ss_sales_price",
                ssj["ss_sales_price"].cast(pa.float64()))
        sa = ssj.group_by(["ss_store_sk", "ss_item_sk"], use_threads=False) \
            .aggregate([("ss_sales_price", "sum"),
                        ("ss_sales_price", "count")]) \
            .rename_columns(["ss_store_sk", "ss_item_sk", "revenue",
                             "sales"])
        sa = sa.set_column(2, "revenue", sa["revenue"].cast(money))
        top = topn(sa, [("sales", "descending"),
                        ("ss_store_sk", "ascending"),
                        ("ss_item_sk", "ascending")])
        if carry is None:
            return top
        year = a[FACT].filter(pc.is_in(a[FACT]["ss_sold_date_sk"],
                                       value_set=dd["d_date_sk"]))
        return top.set_column(2, "revenue",
                              _carried(year, top, carry).cast(money))

    return build, oracle


def _carried(year, top, carry):
    """The revenue of ``top``'s pairs summed again from the year's fact
    rows, each sum carried in numpy type ``carry`` addend by addend, in
    file order (the generator gives these columns no nulls)."""
    def pair(t):
        return (t["ss_store_sk"].to_numpy() << 32) + t["ss_item_sk"].to_numpy()
    want, have = pair(top), pair(year)
    order = np.argsort(want)
    at = np.minimum(np.searchsorted(want[order], have), len(want) - 1)
    hit = want[order][at] == have
    sums = np.zeros(len(want), carry)
    np.add.at(sums, order[at[hit]],
              year["ss_sales_price"].cast(pa.float64()).to_numpy()[hit]
              .astype(carry))
    return pa.array(sums.astype(np.float64))


build, oracle = best_sellers(pa.float64())
