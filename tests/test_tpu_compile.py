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
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


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
    dtype a primitive column, or a string's width."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.columnar.batch import PrimitiveColumn, StringColumn

    def leaf(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    valid = leaf((rows,), jnp.bool_)
    return tuple(
        StringColumn(leaf((rows, kind), jnp.uint8),
                     leaf((rows,), jnp.int32), valid)
        if isinstance(kind, int) else
        PrimitiveColumn(leaf((rows,), kind), valid)
        for kind in layout)


@pytest.mark.parametrize("layout, quota, capacity", [
    # q65's partial state across the all_to_all: (store, item), the
    # double partial sum and the count; a shard of 4 x 2,048 slots
    (("int32", "int32", "float64", "int64"), 2048, 2048),
    # the star joins': a brand string beside its ids and a decimal sum
    # held as one int64 word; 4 x 32,768 slots, a dozen rows a slice
    ((64, "int32", "int32", "int64"), 32768, 16),
], ids=["q65_state", "star_join_string_key"])
def test_the_exchange_read_cut_compiles_for_the_chip(one_chip, layout,
                                                     quota, capacity):
    """The reduce side's read as one program (PR 39): a double partial
    sum is a gathered PAYLOAD in it, never a key or a bitcast, and the
    slice bounds are a small int32 operand."""
    import jax
    import jax.numpy as jnp
    from auron_tpu.parallel import exchange
    cut = exchange._read_cut_kernel(4, capacity)
    bounds = jax.ShapeDtypeStruct((2, 4), jnp.int32, sharding=one_chip)
    compiled = cut.lower(_columns(one_chip, 4 * quota, layout),
                         bounds).compile()
    assert compiled is not None
