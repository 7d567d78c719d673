"""TPC-DS q42: category revenue, one month, one manager.
store_sales JOIN date_dim JOIN item, string-keyed group-by, top 100."""

from planlib import FACT, month_revenue

TABLES = (FACT, "date_dim", "item")

build, oracle = month_revenue("i_category_id", "i_category", "i_manager_id", 1)
