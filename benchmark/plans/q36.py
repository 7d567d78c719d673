"""TPC-DS q36 at its published text: gross margin by the item hierarchy.

    select sum(ss_net_profit)/sum(ss_ext_sales_price) as gross_margin,
           i_category, i_class,
           grouping(i_category)+grouping(i_class) as lochierarchy,
           rank() over (partition by grouping(i_category)+grouping(i_class),
                        case when grouping(i_class) = 0 then i_category end
                        order by sum(ss_net_profit)/sum(ss_ext_sales_price)
                        asc) as rank_within_parent
    from store_sales, date_dim d1, item, store
    where d1.d_year = 2001 and d1.d_date_sk = ss_sold_date_sk
      and i_item_sk = ss_item_sk and s_store_sk = ss_store_sk
      and s_state in (<8 states>)
    group by rollup(i_category, i_class)
    order by lochierarchy desc,
             case when lochierarchy = 0 then i_category end,
             rank_within_parent
    limit 100

As Spark plans it: three joins, Expand (x3) under one aggregation keyed
by (i_category, i_class, spark_grouping_id), a projection of the margin
and the two partition keys, the Window, TakeOrderedAndProject. Money
stays decimal: both sums decimal(17,2), the margin decimal(17,2) /
decimal(17,2) = decimal(37,20). ``grouping(i_category)`` is bit 1 of the
grouping id and ``grouping(i_class)`` bit 0 (ids 0, 1, 3).

Substitution values (``assumed`` in the configuration): the eight states
are drawn as qgen draws them, from the store table — the ``s_state`` of
the eight lowest-numbered stores (``report_states``; a state may repeat,
as the specification's own list repeats one state eight times: dsdgen
puts all twelve stores of SF1 in it). The generator draws each store's
state from twelve, so a FIXED list of eight passes 4 to 11 of the twelve
stores by the seed and a task's work moves by half with it; the rule
passes 8 to 12."""

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

import reportlib as R
from planlib import FACT, join_dim, oj, scan

TABLES = (FACT, "date_dim", "item", "store")

MARGIN = R.divide_type(R.MONEY_SUM, R.MONEY_SUM)        # decimal(37,20)


def report_states(store: pa.Table) -> tuple:
    """The query's eight states: those of the eight lowest-numbered
    stores, in that order."""
    return tuple(store.sort_by("s_store_sk")["s_state"].to_pylist()[:8])


def build(session, tables, splits, partitions=1):
    from auron_tpu.frontend.dataframe import col, functions as F, lit
    states = report_states(pq.read_table(
        tables["store"][0], columns=["s_store_sk", "s_state"]))
    ss = scan(session, tables, FACT, splits, partitions).select(
        "ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
        "ss_ext_sales_price", "ss_net_profit")
    dd = scan(session, tables, "date_dim").filter(col("d_year") == 2001) \
        .select("d_date_sk")
    it = scan(session, tables, "item").select("i_item_sk", "i_category",
                                              "i_class")
    st = scan(session, tables, "store") \
        .filter(col("s_state").isin(*states)).select("s_store_sk")
    j = join_dim(ss, dd, "ss_sold_date_sk", "d_date_sk")
    j = join_dim(j, it, "ss_item_sk", "i_item_sk")
    j = join_dim(j, st, "ss_store_sk", "s_store_sk")
    g = j.rollup("i_category", "i_class").agg(
        F.sum(col("ss_net_profit")).alias("profit"),
        F.sum(col("ss_ext_sales_price")).alias("sales"))
    gid = col("spark_grouping_id")
    g_class = gid % lit(2)
    g = g.select(
        (col("profit") / col("sales")).alias("gross_margin"),
        col("i_category"), col("i_class"),
        (gid / lit(2) + g_class).alias("lochierarchy"),
        R.case_when(g, g_class == lit(0), col("i_category"))
        .alias("_parent"))
    g = g.window([F.rank().alias("rank_within_parent")],
                 partition_by=[col("lochierarchy"), col("_parent")],
                 order_by=[col("gross_margin").asc()])
    top = g.sort(
        col("lochierarchy").desc(),
        R.case_when(g, col("lochierarchy") == lit(0),
                    col("i_category")).asc(),
        col("rank_within_parent").asc()).limit(100)
    return top.select("gross_margin", "i_category", "i_class",
                      "lochierarchy", "rank_within_parent")


def oracle(a, double_division=False):
    dd = a["date_dim"].filter(pc.equal(a["date_dim"]["d_year"], 2001)) \
        .select(["d_date_sk"])
    st = a["store"].filter(pc.is_in(
        a["store"]["s_state"],
        value_set=pa.array(sorted(set(report_states(a["store"])))))) \
        .select(["s_store_sk"])
    it = a["item"].select(["i_item_sk", "i_category", "i_class"])
    j = oj(a[FACT].select(["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                             "ss_ext_sales_price", "ss_net_profit"]),
             dd, ["ss_sold_date_sk"], ["d_date_sk"])
    j = oj(j, it, ["ss_item_sk"], ["i_item_sk"])
    j = oj(j, st, ["ss_store_sk"], ["s_store_sk"])
    sums = {"profit": "ss_net_profit", "sales": "ss_ext_sales_price"}
    rows = []
    for keys, g_cat, g_class in ((["i_category", "i_class"], 0, 0),
                                 (["i_category"], 0, 1), ([], 1, 1)):
        if not keys and j.num_rows == 0:
            continue        # a rollup of no rows has no grand total
        for r in R.group_sums(j, keys, sums):
            rows.append({
                "i_category": r.get("i_category"),
                "i_class": r.get("i_class"), "g_class": g_class,
                "lochierarchy": g_cat + g_class,
                "gross_margin": R.divide(r["profit"], r["sales"], MARGIN,
                                         double_division)})
    # rank() within (lochierarchy, the parent category): 1 + the rows
    # strictly before; ascending puts a null margin first
    for r in rows:
        r["_parent"] = r["i_category"] if r["g_class"] == 0 else None
        r["_order"] = R.sort_key((r["gross_margin"], True))
    for r in rows:
        r["rank_within_parent"] = 1 + sum(
            1 for o in rows
            if (o["lochierarchy"], o["_parent"])
            == (r["lochierarchy"], r["_parent"])
            and o["_order"] < r["_order"])
    rows.sort(key=lambda r: R.sort_key(
        (r["lochierarchy"], False),
        (r["i_category"] if r["lochierarchy"] == 0 else None, True),
        (r["rank_within_parent"], True)))
    rows = rows[:100]
    return pa.table({
        "gross_margin": R.decimal_column(
            [r["gross_margin"] for r in rows], MARGIN),
        "i_category": pa.array([r["i_category"] for r in rows], pa.string()),
        "i_class": pa.array([r["i_class"] for r in rows], pa.string()),
        "lochierarchy": pa.array([r["lochierarchy"] for r in rows],
                                 pa.int64()),
        "rank_within_parent": pa.array(
            [r["rank_within_parent"] for r in rows], pa.int64())})
