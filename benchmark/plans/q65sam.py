"""``q65sa.py`` over money as the specification has it: the hundred
best-selling (store, item) pairs with ``revenue = sum(ss_sales_price)``
kept decimal (decimal(7,2) summed into decimal(17,2), compared exactly),
so the aggregation takes the hash table as ``q65m.py``'s does. The check
plan of that path: its answer is the many-addend groups of the state
that grew and merged over the task's batches."""

import pyarrow as pa

from q65sa import TABLES, best_sellers  # noqa: F401

build, oracle = best_sellers(pa.decimal128(17, 2))
