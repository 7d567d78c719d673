"""Seeded TPC-DS data for the benchmark's cells.

The benchmark's own copy of the repo's real-schema generator
(``auron_tpu/it/tpcds.py``): the TPC-DS table and column names and types
(money as decimal(7,2), surrogate-key joins, nullable foreign keys,
Zipf(1.3) item keys, seasonal dates, ticket coherence), cut to the
tables the plan library reads and with the per-row Python loops replaced
by masks, so that SF1 is made in seconds. dsdgen is not in the image;
the rows are this generator's, the schema and the shape of the
distributions are the specification's.

``scale`` 1.0 is SF1 (2,880,000 ``store_sales`` rows). A smaller scale is
for rehearsals on the CPU only.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

#: d_date_sk of 1998-01-01
DATE_SK0 = 2450815
N_DATES = 365 * 5 + 2          # 1998-01-01 .. 2002-12-31

#: rows at scale 1.0 (TPC-DS SF1; the generator's date_dim is `assumed`)
SF1_ROWS = {"store_sales": 2_880_000, "item": 18_000, "store": 12,
            "date_dim": N_DATES}

TABLES = tuple(SF1_ROWS)


def money_from_cents(cents, precision=7, scale=2):
    """decimal128(p, s) straight from unscaled int64 cents (two int64
    limbs per value, the high limb the sign extension)."""
    cents = np.asarray(cents, np.int64)
    limbs = np.empty((len(cents), 2), np.int64)
    limbs[:, 0] = cents
    limbs[:, 1] = cents >> 63
    return pa.Array.from_buffers(
        pa.decimal128(precision, scale), len(cents),
        [None, pa.py_buffer(limbs.tobytes())])


def _fk(rng, n, n_dim, null_frac=0.0, skew=False):
    """Surrogate keys 1..n_dim, Zipf(1.3)-skewed on request, with a null
    share (TPC-DS fact foreign keys are nullable)."""
    if skew:
        fk = (rng.zipf(1.3, n).astype(np.int64) - 1) % n_dim + 1
    else:
        fk = rng.integers(1, n_dim + 1, n).astype(np.int64)
    if null_frac:
        return pa.array(fk, pa.int64(), mask=rng.random(n) < null_frac)
    return pa.array(fk, pa.int64())


def _calendar():
    doff = np.arange(N_DATES)
    dates = np.datetime64("1998-01-01") + doff
    years = dates.astype("datetime64[Y]").astype(int) + 1970
    months = dates.astype("datetime64[M]").astype(int) % 12 + 1
    return doff, dates, years, months


def date_dim() -> pa.Table:
    doff, dates, years, months = _calendar()
    dom = (dates - dates.astype("datetime64[M]")).astype(int) + 1
    day_names = np.array(["Monday", "Tuesday", "Wednesday", "Thursday",
                          "Friday", "Saturday", "Sunday"])
    return pa.table({
        "d_date_sk": pa.array(DATE_SK0 + doff, pa.int64()),
        "d_date": pa.array(dates.astype("datetime64[D]"), pa.date32()),
        "d_year": pa.array(years.astype(np.int64)),
        "d_moy": pa.array(months.astype(np.int64)),
        "d_dom": pa.array(dom.astype(np.int64)),
        "d_qoy": pa.array(((months - 1) // 3 + 1).astype(np.int64)),
        # 1998-01-01 was a Thursday
        "d_day_name": pa.array(day_names[(doff + 3) % 7]),
        "d_month_seq": pa.array(((years - 1998) * 12 + months - 1)
                                .astype(np.int64)),
        "d_week_seq": pa.array(((doff + 3) // 7 + 5270).astype(np.int64)),
    })


#: The plan library keeps the repo's query parameters (manufacturer 128,
#: managers 1 and 28). dsdgen and qgen choose data and parameters
#: together so that every query returns rows; here the generator pins the
#: two hottest items of the Zipf(1.3) key (a quarter and a tenth of all
#: sales) to those parameters. Every split then has rows for every plan,
#: the work does not hang on whether a seed's few matching items happen
#: to sell, and every answer carries sums past $131,072, where money
#: carried in float32 no longer resolves a cent.
PINNED_ITEMS = {1: {"i_manufact_id": 128, "i_manager_id": 1},
                2: {"i_manager_id": 28}}


def item(rng, n_item: int) -> pa.Table:
    cats = np.array(["Books", "Electronics", "Home", "Jewelry", "Men",
                     "Music", "Shoes", "Sports", "Women", "Children"])
    isk = np.arange(1, n_item + 1)
    cat_idx = rng.integers(0, len(cats), n_item)
    class_id = rng.integers(1, 17, n_item)
    brand_id = rng.integers(1, 1000, n_item)
    ids = {"i_manufact_id": rng.integers(1, 1000, n_item),
           "i_manager_id": rng.integers(1, 100, n_item)}
    for sk, pins in PINNED_ITEMS.items():
        for column, value in pins.items():
            ids[column][sk - 1] = value
    return pa.table({
        "i_item_sk": pa.array(isk, pa.int64()),
        "i_item_id": pa.array([f"AAAAAAAA{k:08d}" for k in isk]),
        "i_item_desc": pa.array([f"item desc {k % 977}" for k in isk]),
        "i_brand_id": pa.array(brand_id, pa.int64()),
        "i_brand": pa.array([f"brand#{b}" for b in brand_id]),
        "i_class_id": pa.array(class_id, pa.int64()),
        "i_class": pa.array([f"class{c:02d}" for c in class_id]),
        "i_category_id": pa.array(cat_idx.astype(np.int64) + 1),
        "i_category": pa.array(cats[cat_idx]),
        "i_manufact_id": pa.array(ids["i_manufact_id"], pa.int64()),
        "i_manufact": pa.array([f"manufact#{m}" for m in
                                rng.integers(1, 100, n_item)]),
        "i_manager_id": pa.array(ids["i_manager_id"], pa.int64()),
        "i_current_price": money_from_cents(rng.integers(9, 9900, n_item)),
        "i_color": pa.array(np.array(
            ["red", "blue", "green", "black", "white", "plum",
             "orchid", "slate"])[rng.integers(0, 8, n_item)]),
        "i_size": pa.array(np.array(
            ["small", "medium", "large", "extra large",
             "economy"])[rng.integers(0, 5, n_item)]),
        "i_units": pa.array(np.array(
            ["Each", "Dozen", "Case", "Pound"])[rng.integers(0, 4, n_item)]),
    })


def store(rng, n_store: int) -> pa.Table:
    states = np.array(["CA", "TX", "NY", "WA", "GA", "OH", "IL", "MI",
                       "TN", "SD", "KY", "FL"])
    cities = np.array(["Fairview", "Midway", "Oak Grove", "Five Points",
                       "Centerville", "Liberty", "Georgetown", "Salem",
                       "Riverside", "Greenfield"])
    ssk = np.arange(1, n_store + 1)
    return pa.table({
        "s_store_sk": pa.array(ssk, pa.int64()),
        "s_store_id": pa.array([f"AAAAAAAA{k:08d}" for k in ssk]),
        "s_store_name": pa.array([f"store_{chr(97 + (k - 1) % 26)}"
                                  for k in ssk]),
        "s_number_employees": pa.array(rng.integers(200, 300, n_store),
                                       pa.int64()),
        "s_city": pa.array(cities[rng.integers(0, len(cities), n_store)]),
        "s_state": pa.array(states[rng.integers(0, len(states), n_store)]),
        "s_market_id": pa.array(rng.integers(1, 11, n_store), pa.int64()),
    })


def store_sales(rng, n_ss: int, n_item: int, n_store: int) -> pa.Table:
    """The fact table, every column of the specification's that the
    repo's generator gives it."""
    _doff, _dates, _years, months = _calendar()
    n_cust, n_addr, n_cd, n_hd, n_promo = 100_000, 50_000, 1920, 7200, 300
    # Nov/Dec sell more (as dsdgen's calendar does)
    date_w = 1.0 + 0.8 * np.isin(months, (11, 12))
    # a ticket is one basket: its lines share customer, store, household,
    # address, date and time
    n_tickets = max(n_ss // 6, 2)
    tk_date = rng.choice(N_DATES, n_tickets,
                         p=date_w / date_w.sum()).astype(np.int64)
    tk_time = rng.integers(0, 1440, n_tickets)
    tk_cust = rng.integers(1, n_cust + 1, n_tickets)
    tk_cust_null = rng.random(n_tickets) < 0.02
    tk_hd = rng.integers(1, n_hd + 1, n_tickets)
    tk_addr = rng.integers(1, n_addr + 1, n_tickets)
    tk_store = rng.integers(1, n_store + 1, n_tickets)
    tickets = rng.integers(0, n_tickets, n_ss).astype(np.int64)
    qty = rng.integers(1, 101, n_ss)
    wholesale_c = rng.integers(100, 10_000, n_ss)         # cents
    list_c = (wholesale_c * (1.0 + rng.random(n_ss) * 1.5)).astype(np.int64)
    sales_c = (list_c * rng.choice([1.0, 1.0, 1.0, 0.9, 0.8, 0.5],
                                   n_ss)).astype(np.int64)
    coupon_c = np.where(rng.random(n_ss) < 0.1,
                        (sales_c * 0.2).astype(np.int64), 0)
    return pa.table({
        "ss_sold_date_sk": pa.array(DATE_SK0 + tk_date[tickets], pa.int64()),
        "ss_sold_time_sk": pa.array(tk_time[tickets], pa.int64()),
        "ss_item_sk": _fk(rng, n_ss, n_item, skew=True),
        "ss_customer_sk": pa.array(tk_cust[tickets], pa.int64(),
                                   mask=tk_cust_null[tickets]),
        "ss_cdemo_sk": _fk(rng, n_ss, n_cd, 0.02),
        "ss_hdemo_sk": pa.array(tk_hd[tickets], pa.int64()),
        "ss_addr_sk": pa.array(tk_addr[tickets], pa.int64()),
        "ss_store_sk": pa.array(tk_store[tickets], pa.int64()),
        "ss_promo_sk": _fk(rng, n_ss, n_promo, 0.05),
        "ss_ticket_number": pa.array(tickets + 1, pa.int64()),
        "ss_quantity": pa.array(qty.astype(np.int64)),
        "ss_wholesale_cost": money_from_cents(wholesale_c),
        "ss_list_price": money_from_cents(list_c),
        "ss_sales_price": money_from_cents(sales_c),
        "ss_ext_sales_price": money_from_cents(sales_c * qty),
        "ss_ext_list_price": money_from_cents(list_c * qty),
        "ss_ext_wholesale_cost": money_from_cents(wholesale_c * qty),
        "ss_coupon_amt": money_from_cents(coupon_c),
        "ss_net_paid": money_from_cents(sales_c * qty - coupon_c),
        "ss_net_profit": money_from_cents(
            (sales_c - wholesale_c) * qty - coupon_c),
    })


def generate(seed: int, scale: float, tables=TABLES) -> dict:
    """{name: pyarrow Table} for ``tables``, the same rows for the same
    (seed, scale) whichever tables are asked for."""
    unknown = set(tables) - set(TABLES)
    if unknown:
        raise ValueError(f"the generator has no table {sorted(unknown)}; "
                         f"it makes {TABLES}")
    n_ss = max(int(round(SF1_ROWS["store_sales"] * scale)), 64)
    n_item = max(int(SF1_ROWS["item"] * min(scale, 1.0)), 200)
    n_store = SF1_ROWS["store"]
    out = {}
    # one stream per table, so leaving a table out moves no other's rows
    streams = np.random.SeedSequence(seed).spawn(3)
    if "date_dim" in tables:
        out["date_dim"] = date_dim()
    if "item" in tables:
        out["item"] = item(np.random.default_rng(streams[0]), n_item)
    if "store" in tables:
        out["store"] = store(np.random.default_rng(streams[1]), n_store)
    if "store_sales" in tables:
        out["store_sales"] = store_sales(
            np.random.default_rng(streams[2]), n_ss, n_item, n_store)
    return out


def write_splits(root: str, name: str, table: pa.Table,
                 split_rows: int) -> list:
    """``table`` as parquet files of ``split_rows`` rows (the last one
    shorter), in row order. Returns [(path, first_row, rows)]. pyarrow's
    writer releases the GIL, so four writers at once make SF1's write
    shorter than the serving process's device start-up beside it."""
    os.makedirs(root, exist_ok=True)

    def write_one(item):
        i, lo = item
        path = os.path.join(root, f"{name}_{i:04d}.parquet")
        chunk = table.slice(lo, split_rows)
        pq.write_table(chunk, path, row_group_size=1 << 16)
        return (path, lo, chunk.num_rows)

    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        return list(pool.map(
            write_one, enumerate(range(0, table.num_rows, split_rows))))


def write_whole(root: str, name: str, table: pa.Table) -> list:
    os.makedirs(root, exist_ok=True)
    path = os.path.join(root, f"{name}.parquet")
    pq.write_table(table, path, row_group_size=1 << 16)
    return [path]
