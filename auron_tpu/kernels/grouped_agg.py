"""Grouped aggregation over a bounded key domain — the dense kernels.

The engine's general aggregation is sort-based (ops/agg.py) and pays
O(n log n) VPU work per batch. When the planner can bound the group-key
domain (table stats, dictionary domains), the aggregation becomes a
dense accumulation problem with two much cheaper formulations:

``pallas_sum_count``
    The Pallas VMEM-accumulate kernel (promoted from
    tools/microbench_pallas.py): per 2048-row block the (hi, lo) one-hot
    tiles are built IN VMEM, the [hi, lo] sum/count grids accumulate IN
    VMEM across the whole grid, and HBM traffic collapses to the
    ~12 B/row inputs. The XLA one-hot formulation materializes
    [n, 256..1024] one-hot operands in HBM (~4 GB per 1M rows).
    ``interpret=True`` runs the same kernel through the Pallas
    interpreter, so it executes (and is differentially verified) under
    ``JAX_PLATFORMS=cpu``; Mosaic compiles it when the dispatch policy
    sees a TPU platform (kernels/dispatch.py).

``dense_matmul_sum_count``
    The one-hot einsum formulation (the flagship ``_q01_kernel`` math),
    lax.map-tiled; compiles everywhere XLA runs.

``scatter_reduce``
    Exact dense-domain scatter (.at[k].add/min/max): the formulation for
    reductions the MXU can't express (min/max) and for integer sums,
    where bit-exactness vs the general path is part of the contract.

Accuracy contract (shared with __graft_entry__._q01_kernel): the f32
value operand is split into 3 additive bf16-exact terms via bit masking,
so a single DEFAULT-precision bf16 MXU pass reproduces f32-HIGHEST
quality (~1e-7 rel); counts are 0/1-exact. Sums accumulate in f32 —
exact whenever inputs are integer-valued and per-key totals stay below
2^24 (the differential battery exploits this for bit-exact checks);
callers wanting exact float-independent sums use ``scatter_reduce``.

Key contract: keys must already lie in [0, key_domain) — callers clip
(the engine additionally tracks the observed key range and fails the
task with a deterministic ValueError when the planner's bound was
wrong, ops/agg.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from auron_tpu.runtime.programs import named

#: lane width of the dense grids: keys decompose as (k >> 8, k & 255) so
#: the minor grid dimension matches the TPU's 256-wide key byte
_LANES = 256

#: second-minor tile granularity for f32 on TPU — the hi grid dimension
#: rounds up to this so Mosaic gets well-shaped blocks
_SUBLANES = 8

#: the hi/lo byte decomposition caps the supported domain
MAX_KEY_DOMAIN = _LANES * _LANES


def grid_dims(key_domain: int) -> tuple[int, int]:
    """(gh, gl) grid shape covering ``key_domain`` keys: gl is the
    256-wide lo byte, gh covers the hi byte rounded up to the f32
    sublane granularity."""
    if not 0 < key_domain <= MAX_KEY_DOMAIN:
        raise ValueError(
            f"key_domain {key_domain} outside (0, {MAX_KEY_DOMAIN}]")
    gh = -(-key_domain // _LANES)
    gh = -(-gh // _SUBLANES) * _SUBLANES
    return gh, _LANES


def _mask16(x):
    """Top-16-bit truncation of f32 via bit masking: exactly
    bf16-representable, and opaque to XLA's bf16-propagation pass (which
    folds convert-based f32->bf16->f32 pairs and would collapse a
    convert-based residual split)."""
    bits = lax.bitcast_convert_type(x, jnp.uint32)
    return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                    jnp.float32)


def _split3(v):
    """f32 -> 3 additive bf16-exact terms (v == v1 + v2 + v3)."""
    v1 = _mask16(v)
    r = v - v1
    v2 = _mask16(r)
    return v1, v2, r - v2


# ---------------------------------------------------------------------------
# Pallas VMEM-accumulate kernel
# ---------------------------------------------------------------------------

def _vmem_agg_kernel(gh, k_ref, v_ref, c_ref, sums_ref, cnts_ref):
    """One grid step: fold a [1, blk] row block into the VMEM-resident
    [gh, 256] sum/count grids. The one-hot tiles never leave VMEM.

    Rows stay on the LANE axis throughout: the one-hots are built
    transposed ([gh, blk] / [256, blk], the [1, blk] inputs broadcast
    along sublanes) and the grids come from an A @ B^T contraction over
    the lane axis — no lane->sublane relayout of the row block and no
    transposed-LHS matmul, neither of which Mosaic lowers."""
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        cnts_ref[:] = jnp.zeros_like(cnts_ref)

    k = k_ref[:]          # [1, blk] int32 in [0, gh * 256)
    v = v_ref[:]          # [1, blk] f32, nulls already zeroed
    c = c_ref[:]          # [1, blk] f32 0/1 valid mask
    blk = k.shape[1]

    hi = (k >> 8) == lax.broadcasted_iota(jnp.int32, (gh, blk), 0)
    lo = ((k & 255) == lax.broadcasted_iota(jnp.int32, (_LANES, blk), 0)
          ).astype(jnp.float32).astype(jnp.bfloat16)

    def grid(vals):
        lhs = jnp.where(hi, vals, jnp.float32(0)).astype(jnp.bfloat16)
        return lax.dot_general(lhs, lo, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    v1, v2, v3 = _split3(v)
    sums_ref[:] += grid(v1) + grid(v2) + grid(v3)
    cnts_ref[:] += grid(c)


def _block_index(*idx):
    """Index-map result pinned to int32: the package enables
    jax_enable_x64, under which a bare ``0`` literal traces as i64 and
    Mosaic refuses the index map."""
    return tuple(jnp.asarray(i, jnp.int32) for i in idx)


@functools.partial(jax.jit,
                   static_argnames=("key_domain", "blk", "interpret"))
@named("auron_kernels_pallas_sum_count")
def pallas_sum_count(k, v, c, key_domain: int, blk: int = 2048,
                     interpret: bool = False):
    """Dense grouped (sum, count) over ``key_domain`` keys.

    k: int32[n] in [0, key_domain); v: f32[n] with nulls zeroed;
    c: f32[n] 0/1 valid mask. n must be a multiple of ``blk`` (batch
    capacities are power-of-two bucketed, so pass blk=min(blk, n)).
    Returns (sums f32[key_domain], counts f32[key_domain]).
    """
    n = k.shape[0]
    blk = min(blk, n)
    if n % blk:
        raise ValueError(f"rows {n} not a multiple of block {blk}")
    gh, gl = grid_dims(key_domain)
    grid = n // blk
    row_block = pl.BlockSpec((1, blk), lambda i: _block_index(0, i))
    out_block = pl.BlockSpec((gh, gl), lambda i: _block_index(0, 0))
    # under shard_map the grids vary over the mesh axes the rows do
    out_grid = jax.ShapeDtypeStruct((gh, gl), jnp.float32,
                                    vma=jax.typeof(k).vma)
    sums, cnts = pl.pallas_call(
        functools.partial(_vmem_agg_kernel, gh),
        out_shape=(out_grid, out_grid),
        grid=(grid,),
        in_specs=[row_block, row_block, row_block],
        out_specs=(out_block, out_block),
        # the output grids accumulate across the whole grid axis
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(k.reshape(1, n), v.reshape(1, n), c.reshape(1, n))
    return sums.reshape(-1)[:key_domain], cnts.reshape(-1)[:key_domain]


# ---------------------------------------------------------------------------
# one-hot matmul formulation (XLA; compiles everywhere)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("key_domain", "block"))
@named("auron_kernels_dense_matmul_sum_count")
def dense_matmul_sum_count(k, v, c, key_domain: int, block: int = 1 << 16):
    """Same contract as ``pallas_sum_count`` via the one-hot einsum
    formulation: lax.map tiles the one-hots so the HBM working set stays
    in tens of MB. This is the flagship ``_q01_kernel`` math, shared so
    the entry point and the engine dispatch one implementation."""
    n = k.shape[0]
    block = min(block, n)
    if n % block:
        raise ValueError(f"rows {n} not a multiple of block {block}")
    gh, gl = grid_dims(key_domain)
    nb = n // block
    kb = k.reshape(nb, block)
    vb = v.reshape(nb, block)
    cb = c.reshape(nb, block)

    def block_grids(inp):
        kk, vals, cnts = inp
        hi = jax.nn.one_hot(kk >> 8, gh, dtype=jnp.float32)
        lo = jax.nn.one_hot(kk & 255, gl, dtype=jnp.float32)
        v1, v2, v3 = _split3(vals)
        lhs = jnp.concatenate(
            [hi * v1[:, None], hi * v2[:, None], hi * v3[:, None],
             hi * cnts[:, None]], axis=1)
        out = jnp.einsum("nh,nl->hl", lhs, lo,
                         precision=lax.Precision.DEFAULT,
                         preferred_element_type=jnp.float32)
        sums = out[:gh] + out[gh:2 * gh] + out[2 * gh:3 * gh]
        return sums, out[3 * gh:]

    sum_blocks, cnt_blocks = lax.map(block_grids, (kb, vb, cb))
    sums = jnp.sum(sum_blocks, axis=0).reshape(-1)[:key_domain]
    cnts = jnp.sum(cnt_blocks, axis=0).reshape(-1)[:key_domain]
    return sums, cnts


def sum_count(k, v, c, key_domain: int, backend: str = "dense_matmul",
              interpret: bool = False, blk: int = 2048):
    """Backend-dispatched dense grouped (sum, count) — the single entry
    the engine and the flagship lowering call with a
    ``kernels.dispatch`` decision."""
    if backend == "pallas_vmem":
        return pallas_sum_count(k, v, c, key_domain, blk=blk,
                                interpret=interpret)
    if backend == "dense_matmul":
        return dense_matmul_sum_count(k, v, c, key_domain)
    raise ValueError(f"unknown dense grouped-agg backend {backend!r}")


# ---------------------------------------------------------------------------
# exact dense-domain scatter reductions
# ---------------------------------------------------------------------------

def scatter_reduce(kind: str, k, v, valid, key_domain: int, dtype):
    """Exact dense reduction via XLA scatter — the formulation for
    reduce kinds the MXU grids can't express (min/max) and for integer
    sums where bit-exactness is contractual. Traffic is the same
    ~12 B/row class as the VMEM kernel (inputs + a [domain] accumulator,
    no one-hot materialization).

    Invalid rows contribute the reduction's neutral; the caller masks
    group existence separately (a key whose rows are all invalid still
    returns the neutral here).
    """
    if kind == "sum":
        vals = jnp.where(valid, v.astype(dtype), jnp.asarray(0, dtype))
        return jnp.zeros(key_domain, dtype).at[k].add(vals, mode="drop")
    if kind == "count":
        ones = valid.astype(jnp.int64)
        return jnp.zeros(key_domain, jnp.int64).at[k].add(ones, mode="drop")
    if kind in ("min", "max"):
        if jnp.issubdtype(dtype, jnp.floating):
            neutral = jnp.asarray(jnp.inf if kind == "min" else -jnp.inf,
                                  dtype)
        else:
            info = jnp.iinfo(dtype)
            neutral = jnp.asarray(info.max if kind == "min" else info.min,
                                  dtype)
        vals = jnp.where(valid, v.astype(dtype), neutral)
        acc = jnp.full(key_domain, neutral, dtype)
        if kind == "min":
            return acc.at[k].min(vals, mode="drop")
        return acc.at[k].max(vals, mode="drop")
    raise ValueError(f"unknown scatter reduction {kind!r}")
