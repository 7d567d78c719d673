"""TPC-DS q65 with money as the specification has it: ``revenue =
sum(ss_sales_price)`` stays decimal (decimal(7,2) summed into the
engine's decimal(17,2), Spark's type for that sum), compared exactly and
decimal in the answer. ``q65.py`` beside it is the library's older form,
which casts money to double before it sums; everything else is the same
plan: store_sales JOIN date_dim (one year) grouped by (store, item),
that aggregate averaged by store, the first aggregate joined with the
second, ``revenue <= 0.1 * ave``, joins to store and item, a sort on two
strings and two numbers, limit 100.

Two departures from the plan Spark makes of the specification's text,
both in the second aggregate and the filter, neither in the money:
``ave = avg(cast(revenue as double))`` over the 12 stores where Spark's
``avg(decimal(17,2))`` is decimal(21,6), and the filter compares
``cast(revenue as double) <= 0.1 * ave`` in double where Spark carries
decimal(21,6) against decimal(23,7). ``d_month_seq`` 24-35 is the
generator's year 2000 (it counts months from 1998-01 = 0; dsdgen's
calendar starts in 1900 and qgen draws 1176-1224)."""

import pyarrow as pa
import pyarrow.compute as pc

from planlib import FACT, join_dim, oj, scan, topn

TABLES = (FACT, "date_dim", "item", "store")

#: sum(decimal(7,2)) as Spark types it
REVENUE = pa.decimal128(17, 2)


def build(session, tables, splits, partitions=1):
    from auron_tpu.columnar.schema import DataType
    from auron_tpu.frontend.dataframe import col, functions as F, lit
    ss = scan(session, tables, FACT, splits, partitions).select(
        "ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_sales_price")
    dd = scan(session, tables, "date_dim").filter(
        (col("d_month_seq") >= 24) & (col("d_month_seq") <= 35)) \
        .select("d_date_sk")
    sa = (join_dim(ss, dd, "ss_sold_date_sk", "d_date_sk")
          .group_by("ss_store_sk", "ss_item_sk")
          .agg(F.sum(col("ss_sales_price")).alias("revenue")))
    sb = (sa.group_by(col("ss_store_sk").alias("st2"))
          .agg(F.avg(col("revenue").cast(DataType.FLOAT64)).alias("ave")))
    j = join_dim(sa, sb, "ss_store_sk", "st2")
    j = j.filter(col("revenue").cast(DataType.FLOAT64)
                 <= col("ave") * lit(0.1))
    st = scan(session, tables, "store").select("s_store_sk", "s_store_name")
    it = scan(session, tables, "item").select("i_item_sk", "i_item_desc",
                                              "i_current_price")
    j = join_dim(j, st, "ss_store_sk", "s_store_sk")
    j = join_dim(j, it, "ss_item_sk", "i_item_sk")
    return (j.select("s_store_name", "i_item_desc", "revenue",
                     "i_current_price")
            .sort(col("s_store_name").asc(), col("i_item_desc").asc(),
                  col("revenue").asc(), col("i_current_price").asc())
            .limit(100))


def oracle(a):
    dd = a["date_dim"].filter(pc.and_(
        pc.greater_equal(a["date_dim"]["d_month_seq"], 24),
        pc.less_equal(a["date_dim"]["d_month_seq"], 35))) \
        .select(["d_date_sk"])
    ssj = oj(a[FACT], dd, ["ss_sold_date_sk"], ["d_date_sk"])
    sa = ssj.group_by(["ss_store_sk", "ss_item_sk"], use_threads=False) \
        .aggregate([("ss_sales_price", "sum")]) \
        .rename_columns(["ss_store_sk", "ss_item_sk", "revenue"])
    sa = sa.set_column(2, "revenue", sa["revenue"].cast(REVENUE))
    sa = sa.append_column("revenue_d", sa["revenue"].cast(pa.float64()))
    sb = sa.group_by(["ss_store_sk"], use_threads=False) \
        .aggregate([("revenue_d", "mean")]).rename_columns(["st2", "ave"])
    j = oj(sa, sb, ["ss_store_sk"], ["st2"])
    j = j.filter(pc.less_equal(j["revenue_d"], pc.multiply(j["ave"], 0.1)))
    j = oj(j, a["store"].select(["s_store_sk", "s_store_name"]),
           ["ss_store_sk"], ["s_store_sk"])
    j = oj(j, a["item"].select(["i_item_sk", "i_item_desc",
                                "i_current_price"]),
           ["ss_item_sk"], ["i_item_sk"])
    g = j.select(["s_store_name", "i_item_desc", "revenue",
                  "i_current_price"])
    return topn(g, [("s_store_name", "ascending"),
                    ("i_item_desc", "ascending"),
                    ("revenue", "ascending"),
                    ("i_current_price", "ascending")])
