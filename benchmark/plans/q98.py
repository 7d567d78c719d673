"""TPC-DS q98 at its published text: an item's share of its class's
revenue in a month.

    select i_item_id, i_item_desc, i_category, i_class, i_current_price,
           sum(ss_ext_sales_price) as itemrevenue,
           sum(ss_ext_sales_price)*100
             / sum(sum(ss_ext_sales_price)) over (partition by i_class)
             as revenueratio
    from store_sales, item, date_dim
    where ss_item_sk = i_item_sk and i_category in (<3>)
      and ss_sold_date_sk = d_date_sk
      and d_date between cast('<day>' as date)
                     and (cast('<day>' as date) + 30 days)
    group by i_item_id, i_item_desc, i_category, i_class, i_current_price
    order by i_category, i_class, i_item_id, i_item_desc, revenueratio

No LIMIT: the whole answer goes back. As Spark types it: ``itemrevenue``
decimal(17,2); times the literal 100 (decimal(3,0)) decimal(21,2); the
windowed sum of sums decimal(27,2); the quotient decimal(21,2) /
decimal(27,2) is 51 digits, cut by adjustPrecisionScale to
decimal(38,17).

Substitution values (``assumed``): the specification's own day
(1999-02-22) and its own categories, Sports, Books and Home — but a
category that holds one of the generator's two pinned items (item 1 sells
a quarter of all rows, item 2 a tenth: its class's revenue would be one
item's, and the task's join twice the rows) gives its place to the next
of the generator's vocabulary (``report_categories``), as qgen draws its
categories from the data."""

import datetime

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

import reportlib as R
from planlib import FACT, join_dim, oj, scan

TABLES = (FACT, "date_dim", "item")

CATEGORIES = ("Sports", "Books", "Home", "Children", "Electronics",
              "Jewelry", "Men", "Music", "Shoes", "Women")
DAY0 = datetime.date(1999, 2, 22)
DAY1 = DAY0 + datetime.timedelta(days=30)
KEYS = ["i_item_id", "i_item_desc", "i_category", "i_class",
        "i_current_price"]

REVENUE = R.MONEY_SUM                                    # decimal(17,2)
TIMES_100 = R.multiply_type(REVENUE, (3, 0))             # decimal(21,2)
CLASS_REVENUE = R.sum_type(REVENUE)                      # decimal(27,2)
RATIO = R.divide_type(TIMES_100, CLASS_REVENUE)          # decimal(38,17)


def report_categories(item: pa.Table) -> tuple:
    """The query's three categories: the first three of ``CATEGORIES``
    that hold neither of the generator's pinned items."""
    pinned = set(item.filter(pc.less_equal(
        item["i_item_sk"], R.PINNED_ITEMS))["i_category"].to_pylist())
    return tuple(c for c in CATEGORIES if c not in pinned)[:3]


def build(session, tables, splits, partitions=1):
    from auron_tpu.frontend.dataframe import col, functions as F
    categories = report_categories(pq.read_table(
        tables["item"][0], columns=["i_item_sk", "i_category"]))
    ss = scan(session, tables, FACT, splits, partitions).select(
        "ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price")
    it = scan(session, tables, "item") \
        .filter(col("i_category").isin(*categories)) \
        .select("i_item_sk", *KEYS)
    dd = scan(session, tables, "date_dim").filter(
        (col("d_date") >= R.date_lit(DAY0))
        & (col("d_date") <= R.date_lit(DAY1))).select("d_date_sk")
    j = join_dim(ss, it, "ss_item_sk", "i_item_sk")
    j = join_dim(j, dd, "ss_sold_date_sk", "d_date_sk")
    g = j.group_by(*KEYS).agg(
        F.sum(col("ss_ext_sales_price")).alias("itemrevenue"))
    g = g.window([F.win_agg("sum", col("itemrevenue")).alias("_class")],
                 partition_by=[col("i_class")])
    out = g.select(
        *KEYS, "itemrevenue",
        (col("itemrevenue") * R.dec_lit("100") / col("_class"))
        .alias("revenueratio"))
    out = out.sort(col("i_category").asc(), col("i_class").asc(),
                   col("i_item_id").asc(), col("i_item_desc").asc(),
                   col("revenueratio").asc())
    # no LIMIT gathers the answer: where the scan has several partitions
    # the sorted runs are collected into the task's one, as the driver
    # collects them
    return out if out.num_partitions == 1 else out.repartition(1)


def oracle(a, double_division=False):
    it = a["item"].filter(pc.is_in(
        a["item"]["i_category"],
        value_set=pa.array(report_categories(a["item"])))) \
        .select(["i_item_sk"] + KEYS)
    dd = a["date_dim"].filter(pc.and_(
        pc.greater_equal(a["date_dim"]["d_date"], pa.scalar(DAY0)),
        pc.less_equal(a["date_dim"]["d_date"], pa.scalar(DAY1)))) \
        .select(["d_date_sk"])
    j = oj(a[FACT].select(["ss_sold_date_sk", "ss_item_sk",
                             "ss_ext_sales_price"]),
             it, ["ss_item_sk"], ["i_item_sk"])
    j = oj(j, dd, ["ss_sold_date_sk"], ["d_date_sk"])
    rows = R.group_sums(j, KEYS, {"itemrevenue": "ss_ext_sales_price"})
    by_class = {}
    for r in rows:
        if r["itemrevenue"] is not None:
            by_class[r["i_class"]] = by_class.get(r["i_class"], 0) \
                + r["itemrevenue"]
    for r in rows:
        times = None if r["itemrevenue"] is None else \
            R.to_type(r["itemrevenue"] * 100, TIMES_100)
        total = R.to_type(by_class.get(r["i_class"]), CLASS_REVENUE) \
            if r["i_class"] in by_class else None
        r["revenueratio"] = R.divide(times, total, RATIO, double_division)
    rows.sort(key=lambda r: R.sort_key(
        (r["i_category"], True), (r["i_class"], True),
        (r["i_item_id"], True), (r["i_item_desc"], True),
        (r["revenueratio"], True)))
    price = a["item"].schema.field("i_current_price").type
    return pa.table({
        "i_item_id": pa.array([r["i_item_id"] for r in rows], pa.string()),
        "i_item_desc": pa.array([r["i_item_desc"] for r in rows],
                                pa.string()),
        "i_category": pa.array([r["i_category"] for r in rows],
                               pa.string()),
        "i_class": pa.array([r["i_class"] for r in rows], pa.string()),
        "i_current_price": pa.array([r["i_current_price"] for r in rows],
                                    price),
        "itemrevenue": R.decimal_column([r["itemrevenue"] for r in rows],
                                        REVENUE),
        "revenueratio": R.decimal_column([r["revenueratio"] for r in rows],
                                         RATIO)})
