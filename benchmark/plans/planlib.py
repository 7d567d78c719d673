"""Shared pieces of the plan library.

A plan file gives ``TABLES`` (what it reads; the fact table first),
``build(session, tables, splits, partitions)`` — the engine's DataFrame
over this task's input splits — and ``oracle(arrow)`` — the same query
in pyarrow/Acero over the same rows. The oracle half imports nothing of
the engine; the engine's DSL is imported inside ``build`` only.

Copied from ``auron_tpu/it/tpcds_queries.py`` with the fact scan's file
list and partition count made arguments.
"""

from __future__ import annotations

import pyarrow.compute as pc

FACT = "store_sales"


# -- engine side -------------------------------------------------------------

def scan(session, tables, name, splits=None, partitions=1):
    """The fact table reads this task's ``splits`` in ``partitions`` scan
    partitions; a dimension reads its whole file in one."""
    if name == FACT:
        return session.read_parquet(list(splits), partitions=partitions)
    return session.read_parquet(tables[name], partitions=1)


def join_dim(fact, dim, fact_key, dim_key, how="inner"):
    """fact JOIN dim USING-style: the dimension's key takes the fact
    key's name and is dropped after the join."""
    from auron_tpu.frontend.dataframe import col
    renamed = dim.select(*[
        col(f.name).alias(fact_key if f.name == dim_key else f.name)
        for f in dim.schema])
    return fact.join(renamed, on=fact_key, how=how)


# -- oracle side -------------------------------------------------------------

def oj(a, b, left, right=None, how="inner"):
    return a.join(b, keys=left, right_keys=right or left, join_type=how)


def topn(t, sort_keys, n=100):
    return t.take(pc.sort_indices(t, sort_keys=sort_keys).slice(0, n))


# -- q42 / q52 / q55: one month's revenue by an item attribute ---------------

def month_revenue(attr_id, attr, flt_col, flt_val):
    def build(session, tables, splits, partitions=1):
        from auron_tpu.frontend.dataframe import col, functions as F
        ss = scan(session, tables, FACT, splits, partitions).select(
            "ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price")
        dd = scan(session, tables, "date_dim") \
            .filter((col("d_moy") == 11) & (col("d_year") == 2000)) \
            .select("d_date_sk", "d_year")
        it = scan(session, tables, "item") \
            .filter(col(flt_col) == flt_val) \
            .select("i_item_sk", attr_id, attr)
        j = join_dim(join_dim(ss, dd, "ss_sold_date_sk", "d_date_sk"),
                     it, "ss_item_sk", "i_item_sk")
        return (j.group_by("d_year", attr_id, attr)
                .agg(F.sum(col("ss_ext_sales_price")).alias("sum_agg"))
                .sort(col("sum_agg").desc(), col(attr_id).asc())
                .limit(100))

    def oracle(a):
        dd = a["date_dim"].filter(
            pc.and_(pc.equal(a["date_dim"]["d_moy"], 11),
                    pc.equal(a["date_dim"]["d_year"], 2000))) \
            .select(["d_date_sk", "d_year"])
        it = a["item"].filter(pc.equal(a["item"][flt_col], flt_val)) \
            .select(["i_item_sk", attr_id, attr])
        j = oj(oj(a[FACT], dd, ["ss_sold_date_sk"], ["d_date_sk"]),
               it, ["ss_item_sk"], ["i_item_sk"])
        g = j.group_by(["d_year", attr_id, attr], use_threads=False) \
            .aggregate([("ss_ext_sales_price", "sum")]) \
            .rename_columns(["d_year", attr_id, attr, "sum_agg"])
        return topn(g, [("sum_agg", "descending"), (attr_id, "ascending")])

    return build, oracle
