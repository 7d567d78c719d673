"""Programs of the main path compiled for the chip, without the chip
(PR 34; tier-1): the TPU's own compiler is installed here and compiles
for a v5e that is described and not attached. Nothing runs, so nothing
here says a result or a time; what it catches is what the chip's
compiler refuses, at no chip time. All such tests stay in THIS file: one
process loads the TPU's library and keeps it.

The topology is described inside a fixture, never while a module is
imported, so every xdist worker collects the same tests.
"""

import os

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    import jax
    return jax.jit(fn).lower(*shapes).compile()


def test_a_double_sort_key_compiles_for_the_chip(one_chip):
    """q65 sorts on a double. The chip carries a double as two float32
    and its compiler rewrites every 64-bit type away, so the IEEE order
    word (a bitcast of the double) was refused there: PR 34's first chip
    run. The pair's order word compiles (4,096 rows: a sort of a full
    scan batch compiles for a minute)."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.ops import sort
    rows = jax.ShapeDtypeStruct((1 << 12,), jnp.float64, sharding=one_chip)

    def permutation(d):
        return jnp.argsort(sort.f64_split_order_word(d), stable=True)

    assert _compile(permutation, rows) is not None


def _columns(one_chip, rows, layout):
    """Shapes of a column tree on the described chip: ``layout`` names a
    dtype a primitive column, a string's width, or ``decimal128`` (the
    two int64 limbs of a decimal past 18 digits)."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import PrimitiveColumn, StringColumn
    from auron_tpu.columnar.decimal128 import Decimal128Column

    def leaf(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    valid = leaf((rows,), jnp.bool_)

    def column(kind):
        if isinstance(kind, int):
            return StringColumn(leaf((rows, kind), jnp.uint8),
                                leaf((rows,), jnp.int32), valid)
        if kind == "decimal128":
            return Decimal128Column(leaf((rows,), jnp.int64),
                                    leaf((rows,), jnp.int64), valid)
        return PrimitiveColumn(leaf((rows,), kind), valid)

    return tuple(column(kind) for kind in layout)


@pytest.mark.parametrize("layouts, quota, window", [
    # q65's partial state across the all_to_all: (store, item), the
    # double partial sum and the count; a shard of 4 x 2,048 slots, its
    # four slices into one batch
    ([("int32", "int32", "float64", "int64")], 2048, 2048),
    # the star joins': a brand string beside its ids and a decimal sum
    # held as one int64 word; 4 x 32,768 slots, a dozen rows a slice
    ([(64, "int32", "int32", "int64")], 32768, 16),
    # q28's: the price (a Decimal64 word) beside the partial state; a
    # band and partition's ~ 750 distinct prices from four sources
    ([("int64", "int64", "int64", "int64")], 32768, 256),
    # the two-round form: eight slices of two shards, source-major and
    # rounds-minor, the rounds' string widths unified before the gather
    ([(64, "int32", "int32", "int64"), (32, "int32", "int32", "int64")],
     2048, 512),
], ids=["q65_state", "star_join_string_key", "q28_price",
        "two_rounds_two_widths"])
def test_the_exchange_read_cut_compiles_for_the_chip(one_chip, layouts,
                                                     quota, window):
    """The reduce side's read as one program (PR 39) that gathers a
    partition's slices — a window a (source, round), stacked — into ONE
    batch (PR 49): a double partial sum is a gathered PAYLOAD in it,
    never a key or a bitcast, and the slice bounds are a small int32
    operand."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.parallel import exchange
    rounds = len(layouts)
    # source-major, rounds-minor: slice k of tree k % rounds
    tree_of_slice = tuple(k % rounds for k in range(4 * rounds))
    cut = exchange._read_cut_kernel(tree_of_slice, window,
                                    len(tree_of_slice) * window)
    bounds = jax.ShapeDtypeStruct((2, len(tree_of_slice)), jnp.int32,
                                  sharding=one_chip)
    trees = tuple(_columns(one_chip, 4 * quota, layout)
                  for layout in layouts)
    compiled = cut.lower(trees, bounds).compile()
    assert compiled is not None


#: the keys a stage's ``ORDER BY`` sorts on: q65's (store name, item
#: description, the double revenue, a decimal held as one int64 word)
#: and a star join's (a year, the decimal sum descending, a brand id)
_SORT_KEYS = {
    "q65": ((16, 64, "float64", "int64"),
            ((0, True), (1, True), (2, True), (3, False))),
    "star_join": (("int32", "int64", "int32", 64),
                  ((0, True), (1, False), (2, True))),
}


def _schema_and_orders(key):
    from auron_tpu.columnar.schema import DataType, Field, Schema
    from auron_tpu.exprs import ir
    layout, keys = _SORT_KEYS[key]
    types = {"int32": DataType.INT32, "int64": DataType.INT64,
             "float64": DataType.FLOAT64}
    schema = Schema(tuple(
        Field(f"c{i}", DataType.STRING if isinstance(k, int) else types[k])
        for i, k in enumerate(layout)))
    orders = tuple(ir.SortOrder(ir.ColumnRef(i), asc, True)
                   for i, asc in keys)
    return layout, schema, orders


@pytest.fixture
def split_double_word(monkeypatch):
    """This process's backend is the CPU, so ``f64_order_word`` would
    hand the chip's compiler the IEEE bitcast it refuses; on the chip
    the fork takes the float32 pair."""
    from auron_tpu.ops import sort
    monkeypatch.setattr(sort, "f64_order_word", sort.f64_split_order_word)


@pytest.mark.parametrize("key", list(_SORT_KEYS))
def test_the_range_split_compiles_for_the_chip(one_chip, key,
                                               split_double_word):
    """The map side's split of a range exchange as ONE program (PR 41):
    the rows' order words compared with the bounds, an operand of
    ``uint64[3, W]`` whose string keys were sampled in a narrower width
    bucket than this batch's, and the sort by partition id."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import DeviceBatch
    from auron_tpu.parallel import exchange
    layout, schema, orders = _schema_and_orders(key)
    rows = 2048
    # a bound's words a key: the null word and one word a value, two
    # for a string sampled at 16 bytes
    bound_layout = tuple(3 if isinstance(layout[o.expr.index], int) else 2
                         for o in orders)
    kern, _built = exchange._fused_split_program(
        (), ("range", orders, bound_layout), schema, schema, 4, rows,
        False, [], (), None, None)

    def leaf(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    batch = DeviceBatch(_columns(one_chip, rows, layout),
                        leaf((), jnp.int32))
    bounds = (leaf((3, sum(bound_layout)), jnp.uint64), leaf((), jnp.int32))
    compiled = kern.lower(batch, leaf((), jnp.int32), leaf((1,), jnp.int64),
                          bounds).compile()
    assert compiled is not None


@pytest.mark.parametrize("key", list(_SORT_KEYS))
def test_the_range_sample_compiles_for_the_chip(one_chip, key,
                                                split_double_word):
    """The sample's word matrix of one batch as ONE program (PR 41)."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import DeviceBatch
    from auron_tpu.parallel import partitioning
    layout, schema, orders = _schema_and_orders(key)
    rows = 2048
    kern = partitioning._range_sample_kernel(orders, schema, rows)
    batch = DeviceBatch(
        _columns(one_chip, rows, layout),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    assert kern.lower(batch).compile() is not None


def test_the_sorts_concatenation_compiles_for_the_chip(one_chip):
    """A reducer's four slices of a range exchange into one batch as ONE
    program (PR 41): q65's row, a double among its payload, two of the
    slices in another string width bucket."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import DeviceBatch
    from auron_tpu.ops import sort
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    batches = tuple(
        DeviceBatch(_columns(one_chip, 256, (16, width, "float64", "int64")),
                    count)
        for width in (64, 128, 64, 128))
    widths = tuple(tuple(leaf.shape[1:] for leaf in
                         jax.tree_util.tree_leaves(b.columns))
                   for b in batches)
    kern = sort._concat_kernel((256,) * 4, widths)
    assert kern.lower(batches).compile() is not None


# -- the ratio reports (PR 42): window, expand, decimal / decimal -----------

def _fields(spec):
    """A schema from (layout kind, DataType name, precision, scale)."""
    from auron_tpu.columnar.schema import DataType, Field, Schema
    return Schema(tuple(
        Field(f"c{i}", getattr(DataType, dt), True, p, s)
        for i, (_kind, dt, p, s) in enumerate(spec)))


#: what the three windows of the ratio reports see: the columns under
#: the window, its partition keys, its order keys, its one function
_WINDOWS = {
    # q36: rank() over (partition by lochierarchy, the parent category
    # order by the decimal(37,20) margin)
    "q36_rank": ((("decimal128", "DECIMAL", 37, 20), (64, "STRING", 0, 0),
                  (64, "STRING", 0, 0), ("int64", "INT64", 0, 0),
                  (64, "STRING", 0, 0)),
                 (3, 4), (0,), ("rank_like", "rank", None)),
    # q53: avg(sum_sales) over (partition by i_manufact_id): decimal(17,2)
    # in one word, decimal(21,6) out in two
    "q53_avg": ((("int64", "INT64", 0, 0), ("int64", "INT64", 0, 0),
                 ("int64", "DECIMAL", 17, 2)),
                (0,), (), ("agg", "avg", 2)),
    # q98: sum(itemrevenue) over (partition by i_class): decimal(27,2)
    "q98_sum": (((32, "STRING", 0, 0), (64, "STRING", 0, 0),
                 (16, "STRING", 0, 0), (16, "STRING", 0, 0),
                 ("int64", "DECIMAL", 7, 2), ("int64", "DECIMAL", 17, 2)),
                (3,), (), ("agg", "sum", 5)),
}


@pytest.mark.parametrize("key", list(_WINDOWS))
def test_the_window_program_compiles_for_the_chip(one_chip, key):
    """The window operator had never met the chip's compiler (PR 42):
    the sort by partition and order keys (strings, and a two-limb
    decimal as the order key), the segment scans on limb pairs and the
    HALF_UP average, each as the ONE program a capacity."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import DeviceBatch
    from auron_tpu.exprs import ir
    from auron_tpu.ops import window
    spec, partition, order, (kind, fn, arg) = _WINDOWS[key]
    rows = 4096
    kern = window._window_kernel(
        tuple(ir.ColumnRef(i) for i in partition),
        tuple(ir.SortOrder(ir.ColumnRef(i), True, True) for i in order),
        (window.WindowFunctionSpec(
            kind, fn, None if arg is None else ir.ColumnRef(arg)),),
        _fields(spec), rows, None)
    batch = DeviceBatch(
        _columns(one_chip, rows, tuple(k for k, *_ in spec)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    assert kern.lower(batch).compile() is not None


class _Source:
    """A stand-in child: the fragments under test only ask its schema."""

    def __init__(self, schema):
        self._schema = schema

    def schema(self):
        return self._schema


def _compile_fragment(one_chip, op, spec, rows):
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import DeviceBatch
    frag = op.build_kernel_fragment()
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    batch = DeviceBatch(
        _columns(one_chip, rows, tuple(k for k, *_ in spec)), scalar)
    carry = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    return frag, _compile(frag.apply, batch, scalar, carry)


def test_the_expand_program_compiles_for_the_chip(one_chip):
    """q36's ROLLUP(i_category, i_class) as the fused stage runs it: a
    full scan batch of joined rows three times over, the rolled-up keys
    null and the grouping id beside them."""
    from auron_tpu.columnar.schema import DataType
    from auron_tpu.exprs import ir
    from auron_tpu.ops.expand import ExpandOp
    spec = (("int64", "DECIMAL", 7, 2), ("int64", "DECIMAL", 7, 2),
            (16, "STRING", 0, 0), (16, "STRING", 0, 0))
    cols = [ir.ColumnRef(i) for i in range(4)]
    null = ir.Literal(None, DataType.STRING)

    def projection(keep, gid):
        return cols + [cols[2] if keep > 0 else null,
                       cols[3] if keep > 1 else null,
                       ir.Literal(gid, DataType.INT32)]

    op = ExpandOp(_Source(_fields(spec)),
                  [projection(2, 0), projection(1, 1), projection(0, 3)])
    frag, compiled = _compile_fragment(one_chip, op, spec, 1 << 16)
    assert compiled is not None
    assert frag.fanout == 3 and frag.row_counts == (("expand_rows_out", 1),)


@pytest.mark.parametrize("left, right", [
    # q36 / q59: one word a side, 123 iterations, decimal(37,20) out
    (("int64", 17, 2), ("int64", 17, 2)),
    # q98: decimal(21,2) / decimal(27,2) = decimal(38,17), two limbs
    (("decimal128", 21, 2), ("decimal128", 27, 2)),
    # q53: decimal(22,6) / decimal(21,6) = decimal(38,16)
    (("decimal128", 22, 6), ("decimal128", 21, 6)),
    # a dividend that passes 128 bits scaled: the four-limb register
    (("decimal128", 38, 2), ("int64", 10, 2)),
], ids=["17_2_by_17_2", "21_2_by_27_2", "22_6_by_21_6", "38_2_by_10_2"])
def test_the_decimal_division_compiles_for_the_chip(one_chip, left, right):
    """decimal / decimal inside a fused projection: a loop of shifts,
    compares and subtracts on int64 limb pairs, on a chip with no 64-bit
    integer unit."""
    from auron_tpu.exprs import ir
    from auron_tpu.ops.project import ProjectOp
    spec = tuple((kind, "DECIMAL", p, s) for kind, p, s in (left, right))
    op = ProjectOp(_Source(_fields(spec)),
                   [ir.BinaryExpr("/", ir.ColumnRef(0), ir.ColumnRef(1))],
                   ["q"])
    frag, compiled = _compile_fragment(one_chip, op, spec, 4096)
    assert compiled is not None
    assert frag.row_counts == (("decimal_div_rows", 1),)


def _on_chip(one_chip, tree):
    """The shapes of ``tree`` (made by ``jax.eval_shape``) placed on the
    described chip."""
    import jax
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)


@pytest.mark.parametrize("mode, rows, slots", [
    # q28's first aggregate: a full scan batch, ~800 of its rows live,
    # into the table at its grown capacity (half a minute to compile)
    ("partial", 1 << 16, 16384),
    # its second (partial_merge): the first's ~6,500 groups in one batch
    ("partial_merge", 8192, 16384),
    # its first since PR 45: the filtered scan batch arrives cut to its
    # live bucket (65,536 -> 1,024), the table at its first capacity
    ("partial", 1024, 4096),
], ids=["scan_batch_into_16384", "partial_merge_of_8192",
        "shrunk_scan_batch_into_4096"])
def test_the_distinct_regroup_compiles_for_the_chip(one_chip, mode, rows,
                                                    slots):
    """The keyed aggregates of a single-DISTINCT plan (PR 44) on the
    chip's hash table, as the programs the operator runs since PR 46:
    the group key is the DISTINCT argument itself, a decimal(7,2) held
    as one int64 word, beside avg's sum and count and count's count.
    ``partial`` evaluates the key and the contributions from the row
    inside the step, ``partial_merge`` reads its child's state columns
    there; the table's set-up rides its first step (PR 51: the fresh
    form, which takes no table), and its export — an argsort of its
    slots — runs inside the emit, cut to the occupancy bucket."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import leaf_layout
    from auron_tpu.exprs import ir
    from auron_tpu.hashtable import agg as ht_agg, core
    from auron_tpu.ops import agg
    price = ("int64", "DECIMAL", 7, 2)
    fns = [ir.AggFunction("avg", ir.ColumnRef(0)),
           ir.AggFunction("count", ir.ColumnRef(0))]
    op = agg.AggOp(_Source(_fields((price,))), [ir.ColumnRef(0)], fns,
                   mode="partial")
    if mode == "partial_merge":
        op = agg.AggOp(op, [ir.ColumnRef(0)],
                       [ir.AggFunction("avg"), ir.AggFunction("count")],
                       mode="partial_merge")
    width = len(op.child.schema())
    columns = _columns(one_chip, rows, ("int64",) * width)
    layout = leaf_layout(columns)
    keys, contribs, _live = agg._front_shapes(op._front, layout, rows)
    key_meta = core.key_meta(keys)
    acc_meta = tuple((kind, str(c.dtype))
                     for kind, c in zip(op._device_kinds(), contribs))
    assert key_meta == (("prim", "int64"),)
    assert acc_meta == (("sum", "int64"),) * 3
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    base = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    fresh = ht_agg._agg_step_kernel(op._front, layout, rows, None,
                                    key_meta, acc_meta, slots, 64)
    assert fresh.lower((), base, columns, scalar,
                       scalar).compile() is not None
    th, tw, store, accs, auxs = _on_chip(one_chip, jax.eval_shape(
        lambda *operands: fresh(*operands),
        (), base, columns, scalar, scalar)[:5])
    step = ht_agg._agg_step_kernel(op._front, layout, rows, key_meta,
                                   key_meta, acc_meta, slots, 64)
    assert step.lower((th, tw, store, accs, auxs), base, columns, scalar,
                      scalar).compile() is not None
    emit = agg._emit_kernel(op.specs, True, slots // 2, key_meta)
    assert emit.lower(th, store, accs).compile() is not None


def test_the_cross_join_compiles_for_the_chip(one_chip):
    """The join without keys (PR 44) as the one program a probe batch: a
    full batch of 65,536 rows beside a one-row build side (q28's answers
    are one row each, at the capacity their aggregation left them)."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import DeviceBatch
    from auron_tpu.ops import joins
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    layout = ("int64", "int64", "int64")       # an average and two counts
    probe = DeviceBatch(_columns(one_chip, 1 << 16, layout), scalar)
    build = DeviceBatch(_columns(one_chip, 4096, layout), scalar)
    cross = joins._cross_program(1 << 16, 4096, 1)
    assert cross.lower(probe, build, scalar).compile() is not None


def test_the_batch_shrink_compiles_for_the_chip(one_chip):
    """A filtered scan batch handed on at its live bucket (PR 45): the
    prefix of every leaf as ONE program, 65,536 -> 1,024 — q28's
    quantity and its three decimal(7,2) money words, beside a two-limb
    decimal and a string."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar import batch as cb
    layout = ("int64",) * 4 + ("decimal128", 32)
    batch = cb.DeviceBatch(
        _columns(one_chip, 1 << 16, layout),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    assert cb.shrink_target(1 << 16, 790) == 1024
    kern = cb._shrink_kernel(cb.leaf_layout(batch.columns), 1 << 16, 1024)
    assert kern.lower(batch).compile() is not None


def test_the_q28_stage_program_compiles_for_the_mesh(topo, tmp_path):
    """The sharded stage program of a q28 band's hash exchange (PR 48),
    for the four chips of the described v5e 2x2: the band's select ->
    filter chain and the ``partial`` aggregate's combine at 65,536 slots
    folded into the ``shard_map`` program, murmur3 of the decimal(7,2)
    key (one int64 word), the split and ``lax.all_to_all`` of the
    ``partial_merge`` state columns at the first quota. No decimal key
    and no aggregate state had crossed the mesh on the chip before; one
    of the six programs (they differ in their filters' constants) takes
    the TPU's compiler over a minute."""
    import sys

    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow.parquet as pq
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from auron_tpu.columnar.batch import PrimitiveColumn
    from auron_tpu.frontend import Session
    from auron_tpu.ir.planner import PlannerContext, plan_from_bytes
    from auron_tpu.parallel import mesh_exchange as mex
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    from auron_tpu.parallel.partitioning import HashPartitioning
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import cell, datagen
    fact = datagen.generate(seed=7, scale=0.002,
                            tables=("store_sales",))["store_sales"]
    paths = []
    for i in range(4):
        paths.append(str(tmp_path / f"store_sales_{i}.parquet"))
        pq.write_table(fact.slice(i * 1000, 1000), paths[-1])
    session = Session()
    try:
        blob = cell.load_module("plans", "q28").build(
            session, {}, paths, 4).task_bytes(0)
    finally:
        session.close()
    found = []

    def walk(op):
        if isinstance(op, ShuffleExchangeOp) \
                and isinstance(op.partitioning, HashPartitioning):
            found.append(op)
        for child in op.children:
            walk(child)

    walk(plan_from_bytes(blob, PlannerContext()))
    assert len(found) == 6 and all(x.combine_mode == "combine"
                                   for x in found)
    exchange = found[0]
    fragments, frag_keys, input_op, combine, sig = exchange._fold_spec()
    assert fragments and combine is not None
    in_schema, axis = input_op.schema(), "data"
    capacity, quota = 1 << 16, 1 << 15      # a scan batch; the first quota
    assert len(in_schema) == 4          # the quantity and three money words
    mesh = Mesh(np.array(topo.devices), (axis,))
    # the registry's key does not hold the mesh: build for this one
    mex._STAGE_EXCHANGE_PROGRAMS.clear()
    try:
        kern, built = mex.stage_exchange_program(
            mesh, axis, 4, frag_keys, ("hash", exchange.partitioning.exprs),
            in_schema, exchange.child.schema(), capacity, quota, fragments,
            exchange.partitioning.exprs, combine, sig)
    finally:
        mex._STAGE_EXCHANGE_PROGRAMS.clear()
    assert built

    def sharded(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    columns = tuple(
        PrimitiveColumn(sharded((4 * capacity,), jnp.int64, P(axis)),
                        sharded((4 * capacity,), jnp.bool_, P(axis)))
        for _ in in_schema)
    compiled = kern.lower(
        columns, sharded((4,), jnp.int32, P(axis)),
        sharded((4, len(fragments)), jnp.int64, P(axis, None))).compile()
    assert "all-to-all" in compiled.as_text()
