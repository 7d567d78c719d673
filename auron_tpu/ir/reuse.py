"""Common-subplan reuse: a subplan two parents share runs once.

A host's front end serialises a TREE, so a DataFrame read by two parents
(q65's ``sa``: the (store, item) aggregate that is both averaged by store
and joined to that average) reaches the planner as two equal subtrees,
and each was planned and run on its own. Spark never does that work
twice (``ReuseExchange`` hands the second parent the first one's
exchange). This pass finds such subtrees on the decoded ``pb.PlanNode``
tree — after the required-columns pass (ir/pruning.py), whose output is
what gets planned — by the plan's own bytes: two subtrees with equal
deterministic serialisations are the same computation. The planner then
plans the subtree once and gives every parent a handle over the one
result (ops/reuse.py).

What is shared, and nothing else:

- the root is an ``agg`` in mode ``complete`` or ``final``: its whole
  output stood in the operator's state an instant before it is emitted,
  so holding it costs no memory the task had not already held. Only
  these nodes are serialised, an aggregation nested in another one once
  more a level of nesting;
- every node under it is of a kind listed in ``_REPLAYABLE``, so its
  leaves are file or memory scans (an ``ipc_reader``, a ``kafka_scan``,
  a shuffle or broadcast read consume their resource by reading, a
  writer or a sink publishes one, a ``debug`` prints);
- every expression in it is of a kind listed in ``_DETERMINISTIC``: no
  ``nullary`` (row numbers, partition ids), no ``host_udf``, no
  ``scalar_subquery`` (the binder substitutes those before it plans),
  and no host-registered aggregate (``udaf:``).

The largest such subtree is taken: equal subtrees inside a shared one
are planned once with it, and a duplicate ``project`` over a shared
``agg`` is planned twice over the one result. The pass reads nothing but
the plan: no option, no plan or table name.
"""

from __future__ import annotations

from auron_tpu.ir import auron_pb2 as pb

#: aggregation modes that emit from a complete state (proto3's unset
#: mode plans as ``complete``)
_WHOLE_OUTPUT_MODES = ("", "complete", "final")
#: node kinds that can run again, or not at all, and nobody can tell
_REPLAYABLE = frozenset({
    "parquet_scan", "orc_scan", "memory_scan", "empty_partitions",
    "filter", "project", "agg", "sort", "limit", "union", "hash_join",
    "sort_merge_join", "window", "expand", "coalesce_batches",
    "rename_columns"})
#: expression kinds whose value is a function of their input row
_DETERMINISTIC = frozenset({
    "column", "literal", "binary", "unary", "cast", "case_when", "in_list",
    "like", "string_pred", "scalar_function", "bloom_might_contain",
    "get_indexed_field", "get_struct_field"})


def find_shared_subplans(plan: pb.PlanNode) -> frozenset:
    """The serialised ``AggNode``s of ``plan`` that more than one parent
    reads and that may be run once (module docstring). Empty for a plan
    with no such duplicate, which is then planned as before."""
    uses: dict = {}
    _count(plan, uses)
    return frozenset(k for k, n in uses.items() if n > 1)


def subplan_key(agg: pb.AggNode) -> bytes:
    return agg.SerializeToString(deterministic=True)


def _count(node: pb.PlanNode, uses: dict) -> None:
    """Count each candidate root as the planner will meet it: the inside
    of a shared subtree is met once, with its first parent."""
    kind = node.WhichOneof("node")
    if kind is None:
        return
    inner = getattr(node, kind)
    if kind == "agg" and inner.mode in _WHOLE_OUTPUT_MODES:
        key = subplan_key(inner)
        if key not in uses:
            uses[key] = 1
        elif uses[key] > 1 or _replayable(inner):
            uses[key] += 1
            return
    for fd, val in inner.ListFields():
        if fd.message_type is pb.PlanNode.DESCRIPTOR:
            for child in (val if fd.is_repeated else (val,)):
                _count(child, uses)


def _replayable(msg) -> bool:
    """Every plan node under ``msg`` is of an understood kind and every
    expression under it deterministic."""
    for fd, val in msg.ListFields():
        if fd.type != fd.TYPE_MESSAGE:
            continue
        for v in (val if fd.is_repeated else (val,)):
            if isinstance(v, pb.PlanNode):
                if v.WhichOneof("node") not in _REPLAYABLE:
                    return False
            elif isinstance(v, pb.ExprNode):
                if v.WhichOneof("expr") not in _DETERMINISTIC:
                    return False
            elif isinstance(v, pb.AggFunctionP) and v.fn.startswith("udaf:"):
                return False
            if not _replayable(v):
                return False
    return True
