"""TPC-DS q59 at its published text: a store's week against the same week
a year later, by day of the week.

    with wss as
     (select d_week_seq, ss_store_sk,
             sum(case when (d_day_name='Sunday') then ss_sales_price
                      else null end) sun_sales,
             ... mon_sales, tue_sales, wed_sales, thu_sales, fri_sales,
             sat_sales
      from store_sales, date_dim
      where d_date_sk = ss_sold_date_sk
      group by d_week_seq, ss_store_sk)
    select s_store_name1, s_store_id1, d_week_seq1,
           sun_sales1/sun_sales2, mon_sales1/mon_sales2,
           tue_sales1/tue_sales2, wed_sales1/wed_sales2,
           thu_sales1/thu_sales2, fri_sales1/fri_sales2,
           sat_sales1/sat_sales2
    from
     (select s_store_name s_store_name1, wss.d_week_seq d_week_seq1,
             s_store_id s_store_id1, sun_sales sun_sales1, ...
      from wss, store, date_dim d
      where d.d_week_seq = wss.d_week_seq and ss_store_sk = s_store_sk
        and d_month_seq between <m> and <m> + 11) y,
     (select s_store_name s_store_name2, wss.d_week_seq d_week_seq2,
             s_store_id s_store_id2, sun_sales sun_sales2, ...
      from wss, store, date_dim d
      where d.d_week_seq = wss.d_week_seq and ss_store_sk = s_store_sk
        and d_month_seq between <m> + 12 and <m> + 23) x
    where s_store_id1 = s_store_id2 and d_week_seq1 = d_week_seq2 - 52
    order by s_store_name1, s_store_id1, d_week_seq1
    limit 100

``wss`` aggregates EVERY row of the split (no date filter under it) by
(week, store) with seven conditional sums, each decimal(17,2), and is
read twice: the planner's common-subplan pass runs it once
(``subplan_reuse_hits`` 1). The join to ``date_dim`` on ``d_week_seq``
is the text's: a week meets each of its days in the month range, so an
answer row comes back once a pair of days (up to 49 times), as it does
from Spark. Each ratio is decimal(17,2) / decimal(17,2) = decimal(37,20).
The seven unnamed ratios are called ``r_sun`` ... ``r_sat``.

Substitution values (``assumed``): ``<m>`` = 36, the generator's year
2001 against 2002 (``d_month_seq`` counts from 1998-01 = 0)."""

import pyarrow as pa
import pyarrow.compute as pc

import reportlib as R
from planlib import FACT, join_dim, oj, scan

TABLES = (FACT, "date_dim", "store")

DAYS = (("sun", "Sunday"), ("mon", "Monday"), ("tue", "Tuesday"),
        ("wed", "Wednesday"), ("thu", "Thursday"), ("fri", "Friday"),
        ("sat", "Saturday"))
MONTH0 = 36
RATIO = R.divide_type(R.MONEY_SUM, R.MONEY_SUM)          # decimal(37,20)


def build(session, tables, splits, partitions=1):
    from auron_tpu.frontend.dataframe import col, functions as F, lit
    ss = scan(session, tables, FACT, splits, partitions).select(
        "ss_sold_date_sk", "ss_store_sk", "ss_sales_price")
    dd = scan(session, tables, "date_dim").select(
        "d_date_sk", "d_week_seq", "d_day_name")
    j = join_dim(ss, dd, "ss_sold_date_sk", "d_date_sk")
    wss = j.group_by("d_week_seq", "ss_store_sk").agg(*[
        F.sum(R.case_when(j, col("d_day_name") == day,
                          col("ss_sales_price"))).alias(f"{nm}_sales")
        for nm, day in DAYS])

    def year(lo, tag):
        d = scan(session, tables, "date_dim").filter(
            (col("d_month_seq") >= lo) & (col("d_month_seq") <= lo + 11)) \
            .select("d_week_seq")
        st = scan(session, tables, "store").select(
            "s_store_sk", "s_store_id", "s_store_name")
        w = join_dim(join_dim(wss, d, "d_week_seq", "d_week_seq"),
                     st, "ss_store_sk", "s_store_sk")
        return w.select(
            col("s_store_name").alias(f"s_store_name{tag}"),
            col("d_week_seq").alias(f"d_week_seq{tag}"),
            col("s_store_id").alias(f"s_store_id{tag}"),
            *[col(f"{nm}_sales").alias(f"{nm}_sales{tag}")
              for nm, _ in DAYS])

    y = year(MONTH0, 1)
    x = year(MONTH0 + 12, 2)
    # s_store_id1 = s_store_id2 and d_week_seq1 = d_week_seq2 - 52
    x = x.select(col("s_store_id2").alias("s_store_id1"),
                 (col("d_week_seq2") - lit(52)).alias("d_week_seq1"),
                 *[col(f"{nm}_sales2") for nm, _ in DAYS])
    # TakeOrderedAndProject: Spark takes the hundred rows, THEN divides
    top = y.join(x, on=["s_store_id1", "d_week_seq1"]) \
        .sort(col("s_store_name1").asc(), col("s_store_id1").asc(),
              col("d_week_seq1").asc()).limit(100)
    return top.select(
        col("s_store_name1"), col("s_store_id1"), col("d_week_seq1"),
        *[(col(f"{nm}_sales1") / col(f"{nm}_sales2")).alias(f"r_{nm}")
          for nm, _ in DAYS])


def oracle(a, double_division=False):
    dd = a["date_dim"]
    j = oj(a[FACT].select(["ss_sold_date_sk", "ss_store_sk",
                             "ss_sales_price"]),
             dd.select(["d_date_sk", "d_week_seq", "d_day_name"]),
             ["ss_sold_date_sk"], ["d_date_sk"])
    # wss: a day's sum is null where the week has no sale on that day
    wss = {}
    for r in R.group_sums(j, ["d_week_seq", "ss_store_sk", "d_day_name"],
                          {"sales": "ss_sales_price"}):
        wss.setdefault((r["d_week_seq"], r["ss_store_sk"]),
                       {})[r["d_day_name"]] = r["sales"]
    store = {r["s_store_sk"]: r for r in a["store"].select(
        ["s_store_sk", "s_store_id", "s_store_name"]).to_pylist()}

    def days_of_week(lo):
        """{d_week_seq: its date_dim rows in the month range}"""
        hit = dd.filter(pc.and_(
            pc.greater_equal(dd["d_month_seq"], lo),
            pc.less_equal(dd["d_month_seq"], lo + 11)))
        counts = {}
        for w in hit["d_week_seq"].to_pylist():
            counts[w] = counts.get(w, 0) + 1
        return counts

    days1, days2 = days_of_week(MONTH0), days_of_week(MONTH0 + 12)
    rows = []
    for (week, sk), first in wss.items():
        second = wss.get((week + 52, sk))
        copies = days1.get(week, 0) * days2.get(week + 52, 0)
        if second is None or not copies or sk not in store:
            continue
        row = (store[sk]["s_store_name"], store[sk]["s_store_id"], week) \
            + tuple(R.divide(first.get(day), second.get(day), RATIO,
                             double_division) for _, day in DAYS)
        rows.extend([row] * copies)
    rows.sort(key=lambda r: r[:3])
    rows = rows[:100]
    cols = {"s_store_name1": pa.array([r[0] for r in rows], pa.string()),
            "s_store_id1": pa.array([r[1] for r in rows], pa.string()),
            "d_week_seq1": pa.array([r[2] for r in rows], pa.int64())}
    for i, (nm, _) in enumerate(DAYS):
        cols[f"r_{nm}"] = R.decimal_column([r[3 + i] for r in rows], RATIO)
    return pa.table(cols)
