"""TPC-DS q65: store/item pairs whose revenue is under a tenth of the
store's average. A high-cardinality (store x item) group-by, a second
aggregate over it and a self-join: the general hash-table aggregation
does the work. In the library for the wide-aggregation cells that a
later PR adds as data; no cell of this PR runs it."""

import pyarrow as pa
import pyarrow.compute as pc

from planlib import FACT, join_dim, oj, scan, topn

TABLES = (FACT, "date_dim", "item", "store")


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
          .agg(F.sum(col("ss_sales_price").cast(DataType.FLOAT64))
               .alias("revenue")))
    sb = (sa.group_by(col("ss_store_sk").alias("st2"))
          .agg(F.avg(col("revenue")).alias("ave")))
    j = join_dim(sa, sb, "ss_store_sk", "st2")
    j = j.filter(col("revenue") <= col("ave") * lit(0.1))
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
    ssj = ssj.set_column(ssj.column_names.index("ss_sales_price"),
                         "ss_sales_price",
                         ssj["ss_sales_price"].cast(pa.float64()))
    sa = ssj.group_by(["ss_store_sk", "ss_item_sk"], use_threads=False) \
        .aggregate([("ss_sales_price", "sum")]) \
        .rename_columns(["ss_store_sk", "ss_item_sk", "revenue"])
    sb = sa.group_by(["ss_store_sk"], use_threads=False) \
        .aggregate([("revenue", "mean")]).rename_columns(["st2", "ave"])
    j = oj(sa, sb, ["ss_store_sk"], ["st2"])
    j = j.filter(pc.less_equal(j["revenue"], pc.multiply(j["ave"], 0.1)))
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
