"""TPC-DS q53 at its published text: a manufacturer's quarter against its
yearly average.

    select * from
      (select i_manufact_id, sum(ss_sales_price) sum_sales,
              avg(sum(ss_sales_price)) over (partition by i_manufact_id)
                avg_quarterly_sales
       from item, store_sales, date_dim, store
       where ss_item_sk = i_item_sk and ss_sold_date_sk = d_date_sk
         and ss_store_sk = s_store_sk
         and d_month_seq in (<12 months>)
         and ((i_category in (<3>) and i_class in (<4>) and i_brand in (<4>))
           or (i_category in (<3>) and i_class in (<4>) and i_brand in (<4>)))
       group by i_manufact_id, d_qoy) tmp1
    where case when avg_quarterly_sales > 0
               then abs(sum_sales - avg_quarterly_sales)
                    / avg_quarterly_sales else null end > 0.1
    order by avg_quarterly_sales, sum_sales, i_manufact_id
    limit 100

As Spark types it: ``sum_sales`` decimal(17,2); the windowed average of
it decimal(21,6); the difference decimal(22,6); the quotient decimal(22,6)
/ decimal(21,6) = decimal(38,16), compared with the literal 0.1
(decimal(1,1)). ``d_qoy`` groups and is not returned.

Substitution values (``assumed`` in the configuration): the twelve
months are the generator's year 2000 (``d_month_seq`` 24-35); categories
and classes are the generator's vocabulary at the specification's list
lengths. **The brands are drawn as qgen draws them, from the data**:
dsdgen's brand belongs to one class of one category, so the text's three
conjuncts select what the brand list selects; this generator draws
category, class and brand independently, and ANY fixed list of 4 brands
of 999 would meet the other two conjuncts in about five items of 18,000,
most of them selling once a year a split — an empty report. So each
arm's four brands are the ``i_brand`` of the four lowest-numbered items
of the arm's categories and classes past the generator's 64 hottest
(``arm_brands``; ``reportlib.HOT_ITEMS``): items that exist under them,
as dsdgen's do, and that sell some hundreds of rows a split whatever the
seed. The plan's text is the specification's, the list's values come
from the item table both sides read."""

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

import reportlib as R
from planlib import FACT, join_dim, oj, scan

TABLES = (FACT, "date_dim", "item", "store")

MONTHS = tuple(range(24, 36))
ARMS = ((("Books", "Children", "Electronics"),
         ("class01", "class02", "class03", "class04")),
        (("Women", "Music", "Men"),
         ("class05", "class06", "class07", "class08")))

SUM_SALES = R.MONEY_SUM                                  # decimal(17,2)
AVG_SALES = R.avg_type(SUM_SALES)                        # decimal(21,6)
DEVIATION = R.divide_type(R.subtract_type(SUM_SALES, AVG_SALES),
                          AVG_SALES)                     # decimal(38,16)


def arm_brands(item: pa.Table) -> list:
    """Each arm's four brands: those of its four lowest-numbered items
    past the generator's hot ones."""
    out = []
    item = item.filter(pc.greater(item["i_item_sk"], R.HOT_ITEMS))
    for cats, classes in ARMS:
        hit = item.filter(pc.and_(
            pc.is_in(item["i_category"], value_set=pa.array(cats)),
            pc.is_in(item["i_class"], value_set=pa.array(classes))))
        hit = hit.sort_by("i_item_sk")
        brands = []
        for b in hit["i_brand"].to_pylist():
            if b not in brands:
                brands.append(b)
            if len(brands) == 4:
                break
        out.append(tuple(brands))
    return out


def build(session, tables, splits, partitions=1):
    from auron_tpu.frontend.dataframe import col, functions as F
    item = pq.read_table(tables["item"][0],
                         columns=["i_item_sk", "i_category", "i_class",
                                  "i_brand"])
    arms = None
    for (cats, classes), brands in zip(ARMS, arm_brands(item)):
        arm = (col("i_category").isin(*cats) & col("i_class").isin(*classes)
               & col("i_brand").isin(*brands))
        arms = arm if arms is None else arms | arm
    ss = scan(session, tables, FACT, splits, partitions).select(
        "ss_sold_date_sk", "ss_item_sk", "ss_store_sk", "ss_sales_price")
    it = scan(session, tables, "item").filter(arms) \
        .select("i_item_sk", "i_manufact_id")
    dd = scan(session, tables, "date_dim") \
        .filter(col("d_month_seq").isin(*MONTHS)) \
        .select("d_date_sk", "d_qoy")
    st = scan(session, tables, "store").select("s_store_sk")
    j = join_dim(ss, it, "ss_item_sk", "i_item_sk")
    j = join_dim(j, dd, "ss_sold_date_sk", "d_date_sk")
    j = join_dim(j, st, "ss_store_sk", "s_store_sk")
    g = j.group_by("i_manufact_id", "d_qoy").agg(
        F.sum(col("ss_sales_price")).alias("sum_sales"))
    g = g.window([F.win_agg("avg", col("sum_sales"))
                  .alias("avg_quarterly_sales")],
                 partition_by=[col("i_manufact_id")])
    avg = col("avg_quarterly_sales")
    deviation = R.case_when(
        g, avg > R.dec_lit("0"), F.abs(col("sum_sales") - avg) / avg)
    out = g.filter(deviation > R.dec_lit("0.1")) \
        .select("i_manufact_id", "sum_sales", "avg_quarterly_sales")
    return out.sort(col("avg_quarterly_sales").asc(),
                    col("sum_sales").asc(),
                    col("i_manufact_id").asc()).limit(100)


def oracle(a, double_division=False):
    item = a["item"]
    arms = None
    for (cats, classes), brands in zip(ARMS, arm_brands(item)):
        arm = pc.and_(pc.and_(
            pc.is_in(item["i_category"], value_set=pa.array(cats)),
            pc.is_in(item["i_class"], value_set=pa.array(classes))),
            pc.is_in(item["i_brand"], value_set=pa.array(brands)))
        arms = arm if arms is None else pc.or_(arms, arm)
    it = item.filter(arms).select(["i_item_sk", "i_manufact_id"])
    dd = a["date_dim"].filter(pc.is_in(
        a["date_dim"]["d_month_seq"], value_set=pa.array(MONTHS))) \
        .select(["d_date_sk", "d_qoy"])
    j = oj(a[FACT].select(["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
                             "ss_sales_price"]),
             it, ["ss_item_sk"], ["i_item_sk"])
    j = oj(j, dd, ["ss_sold_date_sk"], ["d_date_sk"])
    j = oj(j, a["store"].select(["s_store_sk"]),
             ["ss_store_sk"], ["s_store_sk"])
    rows = R.group_sums(j, ["i_manufact_id", "d_qoy"],
                        {"sum_sales": "ss_sales_price"})
    by_manufact = {}
    for r in rows:
        by_manufact.setdefault(r["i_manufact_id"], []).append(r)
    kept = []
    for group in by_manufact.values():
        sums = [r["sum_sales"] for r in group if r["sum_sales"] is not None]
        avg = R.to_type(sum(sums) / len(sums), AVG_SALES) if sums else None
        for r in group:
            deviation = None
            if avg is not None and avg > 0 and r["sum_sales"] is not None:
                deviation = R.divide(abs(r["sum_sales"] - avg), avg,
                                     DEVIATION, double_division)
            if deviation is not None and deviation > R.Decimal("0.1"):
                kept.append({**r, "avg_quarterly_sales": avg})
    kept.sort(key=lambda r: R.sort_key((r["avg_quarterly_sales"], True),
                                       (r["sum_sales"], True),
                                       (r["i_manufact_id"], True)))
    kept = kept[:100]
    return pa.table({
        "i_manufact_id": pa.array([r["i_manufact_id"] for r in kept],
                                  pa.int64()),
        "sum_sales": R.decimal_column([r["sum_sales"] for r in kept],
                                      SUM_SALES),
        "avg_quarterly_sales": R.decimal_column(
            [r["avg_quarterly_sales"] for r in kept], AVG_SALES)})
