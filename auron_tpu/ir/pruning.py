"""Required-columns pass: a file scan reads the columns its plan reads.

A Spark host prunes in Catalyst and ships every scan with its required
columns already named (the reference's parquet opener reads only those:
datafusion-ext-plans/src/parquet_exec.rs:151-237). This engine's own
``Session`` front end ships ``read_parquet(files)`` whole and wraps a
``project`` round it, so the planner does the pruning itself, on the
decoded ``pb.PlanNode`` tree before any operator is built: walk top-down
carrying which of a node's output columns its parent reads, name the
survivors in the scan's ``columns`` (the seam ``io/parquet.py`` already
honours) and re-index the ``ColumnRefE``s bound to the scan's output.

Node kinds with a rule:

- ``parquet_scan`` / ``orc_scan``: keep the columns asked for plus those
  the scan's own pushed-down predicates name, in the order the scan
  already emits them (file order, or the caller's ``columns``: a stable
  order keeps one program a shape). A chain that reads no column
  (``count(*)``) keeps the first fixed-width one, since a batch's row
  count rides on its columns.
- ``filter``, ``sort``, ``limit``, ``coalesce_batches``, ``debug``: the
  output is the child's, so the child owes what the parent reads plus
  what the node's own expressions name.
- ``project``, ``agg`` (partial / complete): the node fixes its own
  output from expressions over its child, so the child owes exactly the
  columns those expressions name and nothing above the node moves.

Every other kind reads every column of its children and is left as it
came (the walk goes on below it: a ``project`` under a join starts a
chain of its own). A ``scalar_subquery``'s plan is its own tree: it is
pruned when the binder plans it, and its column references are never
taken for the enclosing node's. ``memory_scan``, ``ipc_reader`` and
``kafka_scan`` have no ``columns`` and are never touched. The pass reads
nothing but the plan's own expressions: no option, no table or plan name.
"""

from __future__ import annotations

from typing import Iterator, Optional

from auron_tpu.ir import auron_pb2 as pb

#: kind -> the fields whose expressions are bound to the child's output,
#: for nodes whose output IS their child's ...
_PASS_THROUGH = {"filter": ("predicates",), "sort": ("sort_orders",),
                 "limit": (), "coalesce_batches": (), "debug": ()}
#: ... and for nodes that fix their own output
_FIXES_OUTPUT = {"project": ("exprs",), "agg": ("group_exprs", "aggs")}
_SCAN_FORMATS = {"parquet_scan": "parquet", "orc_scan": "orc"}
_VARIABLE_WIDTH = (pb.DT_STRING, pb.DT_LIST, pb.DT_MAP, pb.DT_STRUCT)


def prune_scan_columns(plan: pb.PlanNode) -> pb.PlanNode:
    """A copy of ``plan`` whose file scans name the columns the plan
    reads. The root's parent reads all of the root's output."""
    out = pb.PlanNode()
    out.CopyFrom(plan)
    _prune(out, None)
    return out


def _column_refs(msg) -> Iterator[pb.ColumnRefE]:
    """Every ColumnRefE under ``msg`` that is bound to the same input as
    ``msg`` itself: a scalar subquery's plan is another scope."""
    for fd, val in msg.ListFields():
        if fd.type != fd.TYPE_MESSAGE:
            continue
        for v in (val if fd.is_repeated else (val,)):
            if isinstance(v, pb.ColumnRefE):
                yield v
            elif not isinstance(v, (pb.ScalarSubqueryE, pb.PlanNode)):
                yield from _column_refs(v)


def _prune(node: pb.PlanNode,
           required: Optional[frozenset]) -> Optional[dict]:
    """Narrow the scans under ``node`` to ``required``, the indices of
    ``node``'s output its parent reads (None: all of them). Returns the
    old -> new index map of ``node``'s output where that changed."""
    kind = node.WhichOneof("node")
    if kind is None:
        return None
    inner = getattr(node, kind)
    if kind in _SCAN_FORMATS:
        return _prune_scan(inner, required, _SCAN_FORMATS[kind])
    # a final agg reads its child's partial layout whole, by position
    fixes = kind in _FIXES_OUTPUT and not (
        kind == "agg" and inner.mode not in ("", "partial", "complete"))
    if fixes or kind in _PASS_THROUGH:
        fields = _FIXES_OUTPUT[kind] if fixes else _PASS_THROUGH[kind]
        refs = [r for f in fields for m in getattr(inner, f)
                for r in _column_refs(m)]
        named = frozenset(r.index for r in refs)
        if fixes:
            need = named
        else:
            need = None if required is None else required | named
        remap = _prune(inner.child, need)
        if remap:
            for r in refs:
                r.index = remap[r.index]
        return None if fixes else remap
    # no rule: the node reads all of its children and stays as it came
    for fd, val in inner.ListFields():
        if fd.message_type is pb.PlanNode.DESCRIPTOR:
            for child in (val if fd.is_repeated else (val,)):
                _prune(child, None)
    return None


def _prune_scan(scan, required: Optional[frozenset],
                fmt: str) -> Optional[dict]:
    if required is None:
        return None
    refs = [r for p in getattr(scan, "predicates", ())
            for r in _column_refs(p)]
    need = set(required).union(r.index for r in refs)
    names = list(scan.columns)
    fields = list(scan.schema.fields)
    if names and len(need) >= len(names):
        return None            # a host's scan, already pruned: no file read
    file_schema = None
    if not names or (not need and not fields):
        # a scan that names no columns emits the file's, in file order;
        # picking count(*)'s one column needs the types
        from auron_tpu.io.parquet import file_schema as read_file_schema
        file_schema = read_file_schema(scan.files, fmt)
        names = names or list(file_schema.names)
    if len(set(names)) != len(names) \
            or (fields and len(fields) != len(names)) \
            or not all(0 <= i < len(names) for i in need):
        return None            # not a plan this pass understands
    if not need:
        from pyarrow import types as pat
        fixed = [f.dtype not in _VARIABLE_WIDTH for f in fields] \
            or [pat.is_primitive(t) or pat.is_decimal(t)
                for t in (file_schema.field(n).type for n in names)]
        need = {fixed.index(True) if True in fixed else 0}
    keep = sorted(need)
    narrowed = len(keep) < len(names)
    if fields:
        if narrowed:
            del scan.schema.fields[:]
            scan.schema.fields.extend(fields[i] for i in keep)
    elif file_schema is not None:
        # the file is open here: hand the operator its schema, so that
        # building it does not open the file a second time
        import pyarrow as pa
        from auron_tpu.columnar.arrow_bridge import schema_from_arrow
        from auron_tpu.ir.serde import schema_to_proto
        scan.schema.CopyFrom(schema_to_proto(schema_from_arrow(
            pa.schema([file_schema.field(names[i]) for i in keep]))))
    if not narrowed:
        return None
    scan.columns[:] = [names[i] for i in keep]
    remap = {old: new for new, old in enumerate(keep)}
    for r in refs:
        r.index = remap[r.index]
    return remap
