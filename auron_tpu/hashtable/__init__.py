"""Device-resident vectorized hash table (the engine's missing data
structure: reference AggExec/JoinHashMap are open-addressing tables,
agg_table.rs:68-356 + join_hash_map.rs:44-365).

Three public operations, all built from JAX primitives and traceable
into any jit program:

- ``build``  — insert key columns, get stable slot ids
  (``DeviceHashTable.insert`` / the traced ``core.insert_loop``);
- ``probe``  — lookup-only (``DeviceHashTable.probe``, and the
  hash-join candidate index ``build_join_index``/``JoinHashIndex``);
- ``agg_update`` — slot-indexed accumulator scatters
  (``core.agg_update``; fused per-batch into ``HashAggState.update``).

Every compile site registers with the central program-cache registry
(runtime/programs.py): hashtable.agg_step / agg_grow /
agg_export / build / probe / grow / join_index — visible in tools/compile_report.py
and bounded by ``auron.max_live_programs``.
"""

from auron_tpu.hashtable.agg import (HashAggState, HashTableOverflow,
                                     grouped_agg_once)
from auron_tpu.hashtable.core import SUPPORTED_KINDS
from auron_tpu.hashtable.table import (DeviceHashTable, JoinHashIndex,
                                       build_join_index)

__all__ = [
    "DeviceHashTable", "HashAggState", "HashTableOverflow",
    "JoinHashIndex", "SUPPORTED_KINDS", "build_join_index",
    "grouped_agg_once",
]
