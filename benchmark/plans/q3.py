"""TPC-DS q3: yearly brand revenue for one manufacturer in November.
store_sales JOIN date_dim JOIN item, string-keyed group-by, top 100."""

import pyarrow.compute as pc

from planlib import FACT, join_dim, oj, scan, topn

TABLES = (FACT, "date_dim", "item")


def build(session, tables, splits, partitions=1):
    from auron_tpu.frontend.dataframe import col, functions as F
    ss = scan(session, tables, FACT, splits, partitions).select(
        "ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price")
    dd = scan(session, tables, "date_dim").filter(col("d_moy") == 11) \
        .select("d_date_sk", "d_year")
    it = scan(session, tables, "item") \
        .filter(col("i_manufact_id") == 128) \
        .select("i_item_sk", "i_brand_id", "i_brand")
    j = join_dim(join_dim(ss, dd, "ss_sold_date_sk", "d_date_sk"),
                 it, "ss_item_sk", "i_item_sk")
    return (j.group_by("d_year", "i_brand_id", "i_brand")
            .agg(F.sum(col("ss_ext_sales_price")).alias("sum_agg"))
            .sort(col("d_year").asc(), col("sum_agg").desc(),
                  col("i_brand_id").asc())
            .limit(100))


def oracle(a):
    dd = a["date_dim"].filter(pc.equal(a["date_dim"]["d_moy"], 11)) \
        .select(["d_date_sk", "d_year"])
    it = a["item"].filter(pc.equal(a["item"]["i_manufact_id"], 128)) \
        .select(["i_item_sk", "i_brand_id", "i_brand"])
    j = oj(oj(a[FACT], dd, ["ss_sold_date_sk"], ["d_date_sk"]),
           it, ["ss_item_sk"], ["i_item_sk"])
    g = j.group_by(["d_year", "i_brand_id", "i_brand"], use_threads=False) \
        .aggregate([("ss_ext_sales_price", "sum")]) \
        .rename_columns(["d_year", "i_brand_id", "i_brand", "sum_agg"])
    return topn(g, [("d_year", "ascending"), ("sum_agg", "descending"),
                    ("i_brand_id", "ascending")])
