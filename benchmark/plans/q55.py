"""TPC-DS q55: brand revenue for another manager.
store_sales JOIN date_dim JOIN item, string-keyed group-by, top 100."""

from planlib import FACT, month_revenue

TABLES = (FACT, "date_dim", "item")

build, oracle = month_revenue("i_brand_id", "i_brand", "i_manager_id", 28)
