"""Kernel-selection policy for grouped aggregation.

The planner-facing decision point: given what the plan knows about an
aggregation (key-domain bound, key/value dtypes, aggregate set) and
what the environment provides (platform, config), pick one of

- ``pallas_vmem``   — the VMEM-accumulate Pallas kernel
                      (kernels/grouped_agg.pallas_sum_count): compiled
                      by Mosaic on a TPU, run through the Pallas
                      interpreter elsewhere. A Mosaic failure surfaces
                      as the compile error it is — nothing here swaps
                      in another kernel;
- ``dense_matmul``  — the one-hot einsum formulation (compiles on any
                      XLA backend);
- ``sort``          — the general sort-based AggOp path (unbounded
                      domains, every dtype): the fallback.

Every decision is counted (kernels/registry.py + the per-task
MetricsSet under the ``kernels`` key) so "which kernel ran and why"
is answerable from the existing metrics snapshot.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from auron_tpu.columnar.schema import DataType
from auron_tpu.kernels import grouped_agg, registry

#: aggregate functions the dense-domain path finalizes (ops/agg.py
#: _DenseDomainState); first/collect/distinct/bloom/udaf stay sort-based
DENSE_AGG_FNS = frozenset(
    {"count", "count_star", "sum", "avg", "min", "max"})

#: integer-class key dtypes the (hi, lo) byte decomposition accepts
DENSE_KEY_DTYPES = frozenset(
    {DataType.INT8, DataType.INT16, DataType.INT32, DataType.INT64})

#: value dtypes with a dense accumulator formulation (floats via the
#: MXU grids, integers/dates via exact scatter)
DENSE_VALUE_DTYPES = frozenset(
    {DataType.INT8, DataType.INT16, DataType.INT32, DataType.INT64,
     DataType.FLOAT32, DataType.FLOAT64, DataType.DATE32})

#: rough HBM-traffic estimates, bytes per input row (the VMEM kernel
#: reads k/v/c once: 12 B/row; the matmul path materializes one-hot +
#: lhs operands in HBM: ~(5*gh + gl)*4 at the full 256x256 grid; the
#: sort path re-reads rows across hash/sort/segment passes)
BYTES_PER_ROW = {"pallas_vmem": 12, "dense_matmul": 6144, "sort": 48}


@dataclass(frozen=True)
class KernelDecision:
    kernel: str              # pallas_vmem | dense_matmul | sort
    interpret: bool          # pallas interpreter (non-TPU platforms)
    reason: str              # why this kernel (or why the fallback)
    bytes_per_row: int       # HBM-traffic estimate for metrics

    @property
    def is_dense(self) -> bool:
        return self.kernel != "sort"


def _platform(platform: Optional[str]) -> str:
    if platform is not None:
        return platform
    import jax
    return jax.default_backend()


def backend_for_platform(conf=None, platform: Optional[str] = None
                         ) -> tuple[str, bool]:
    """(backend, interpret) honoring ``auron.kernels.backend``.

    ``auto`` picks the Pallas kernel natively on a real TPU and the
    one-hot matmul formulation elsewhere; ``pallas`` forces the Pallas
    kernel, through the interpreter on non-TPU platforms (how the
    differential battery runs it under JAX_PLATFORMS=cpu)."""
    from auron_tpu import config as cfg
    conf = conf or cfg.get_config()
    choice = conf.get(cfg.KERNELS_BACKEND)
    plat = _platform(platform)
    if choice == "pallas":
        return "pallas_vmem", plat != "tpu"
    if choice == "dense":
        return "dense_matmul", False
    if choice == "sort":
        return "sort", False
    if choice != "auto":
        raise ValueError(
            f"auron.kernels.backend: unknown backend {choice!r} "
            "(auto|pallas|dense|sort)")
    if plat == "tpu":
        return "pallas_vmem", False
    return "dense_matmul", False


def _count(metrics, name: str, v: int = 1) -> None:
    if metrics is not None:
        metrics.counter(name).add(v)


def record_rows(decision: KernelDecision, rows: int, metrics=None) -> None:
    """Accumulate the bytes-moved estimate for ``rows`` input rows
    against the decision's kernel (registry + per-task metrics)."""
    est = rows * decision.bytes_per_row
    registry.stats(decision.kernel).add("bytes_moved_est", est)
    _count(metrics, "bytes_moved_est", est)


@dataclass(frozen=True)
class HashAggDecision:
    """The general-path grouping decision: hashtable vs sort."""
    backend: str             # hashtable | sort
    reason: str
    load_factor: float = 0.5
    max_probe_rounds: int = 64

    @property
    def is_hash(self) -> bool:
        return self.backend == "hashtable"


#: key column dtypes with a hashtable word encoding; nested types
#: (STRUCT/LIST/MAP) stay on the sort path
HASH_KEY_DTYPES = frozenset(
    {DataType.BOOL, DataType.INT8, DataType.INT16, DataType.INT32,
     DataType.INT64, DataType.FLOAT32, DataType.FLOAT64,
     DataType.DATE32, DataType.TIMESTAMP_US, DataType.STRING,
     DataType.DECIMAL})


def record_operator_choice(metrics, backend: str) -> None:
    """Mirror the chosen grouping backend into the OPERATOR's metrics
    (not just the shared ``kernels`` set), so the finalize snapshot
    shows which backend each operator actually ran."""
    _count(metrics, f"dispatch_{backend}")


def select_hash_agg(*, key_dtypes, acc_kinds, has_float_sum: bool,
                    conf=None, metrics=None,
                    record: bool = True) -> HashAggDecision:
    """The general (unbounded-key) grouping decision: the device hash
    table (auron_tpu/hashtable) or the sort + segment-reduce path.

    key_dtypes: DataType per group key (nested types fall back).
    acc_kinds: flat device reduce kinds (ops/agg._device_kinds).
    has_float_sum: any float-dtype 'sum' accumulator — reassociation
    changes last-ulp results, so 'auto' keeps those on the sort path and
    only auron.hashtable.backend=hash forces them through the table.
    """
    from auron_tpu import config as cfg
    from auron_tpu.hashtable import SUPPORTED_KINDS
    conf = conf or cfg.get_config()

    def decide(backend: str, reason: str) -> HashAggDecision:
        if record:
            event = "selected" if backend == "hashtable" else "fallback"
            registry.stats("hashtable").add(event)
            _count(metrics, f"hashtable_{event}")
        return HashAggDecision(
            backend, reason,
            load_factor=conf.get(cfg.HASHTABLE_LOAD_FACTOR),
            max_probe_rounds=max(1, conf.get(
                cfg.HASHTABLE_MAX_PROBE_ROUNDS)))

    if not conf.get(cfg.HASHTABLE_ENABLED):
        return decide("sort", "disabled")
    choice = conf.get(cfg.HASHTABLE_BACKEND)
    if choice == "sort":
        return decide("sort", "backend_config")
    if choice not in ("auto", "hash"):
        raise ValueError(
            f"auron.hashtable.backend: unknown backend {choice!r} "
            "(auto|hash|sort)")
    kds = tuple(key_dtypes)
    if not kds:
        return decide("sort", "no_keys")
    bad = [d for d in kds if d not in HASH_KEY_DTYPES]
    if bad:
        return decide("sort", f"key_dtype:{bad[0].value}")
    for kind in acc_kinds:
        if kind not in SUPPORTED_KINDS:
            return decide("sort", f"acc_kind:{kind}")
    if has_float_sum and choice != "hash":
        # scatter-add reassociates float sums; 'auto' keeps results
        # bit-identical to the sort path by falling back
        return decide("sort", "float_sum_inexact")
    return decide("hashtable", "eligible")


def select_grouped_agg(*, key_domain: Optional[int], key_dtypes,
                       agg_fns, value_dtypes, conf=None, metrics=None,
                       platform: Optional[str] = None,
                       record: bool = True) -> KernelDecision:
    """The grouped-agg kernel decision.

    key_domain: exclusive upper bound on the (non-negative) group keys,
    or None when unbounded. key_dtypes/value_dtypes: DataType per group
    key / aggregate argument. agg_fns: AccSpec.fn per aggregate.
    ``metrics``: a MetricsSet (usually ctx.metrics_for("kernels")) that
    receives selected/fallback/interpret counters alongside the
    process-global registry stats. ``record=False`` returns the pure
    policy decision without touching any counter — for callers that
    override the fallback and account for the kernel they actually run
    themselves (the flagship lowering)."""
    from auron_tpu import config as cfg
    conf = conf or cfg.get_config()

    def fallback(reason: str) -> KernelDecision:
        if record:
            registry.stats("sort").add("selected")
            registry.stats("sort").add("fallback")
            _count(metrics, "sort_selected")
            _count(metrics, "fallback")
        return KernelDecision("sort", False, reason,
                              BYTES_PER_ROW["sort"])

    if not conf.get(cfg.KERNELS_ENABLED):
        return fallback("disabled")
    if key_domain is None:
        return fallback("unbounded_key_domain")
    if key_domain <= 0:
        return fallback("empty_key_domain")
    if key_domain > min(conf.get(cfg.KERNELS_MAX_KEY_DOMAIN),
                        grouped_agg.MAX_KEY_DOMAIN):
        return fallback("key_domain_too_large")
    kds = tuple(key_dtypes)
    if len(kds) != 1:
        # the dense grids decompose ONE integer key as (hi, lo) bytes;
        # composite keys stay on the sort path
        return fallback("multi_key" if kds else "no_key")
    bad = [d for d in kds if d not in DENSE_KEY_DTYPES]
    if bad:
        return fallback(f"key_dtype:{bad[0].value}")
    for fn in agg_fns:
        if fn not in DENSE_AGG_FNS:
            return fallback(f"agg_fn:{fn}")
    for d in value_dtypes:
        if d not in DENSE_VALUE_DTYPES:
            return fallback(f"value_dtype:{d.value}")

    backend, interpret = backend_for_platform(conf, platform)
    if backend == "sort":
        return fallback("backend_config")
    if record:
        registry.stats(backend).add("selected")
        _count(metrics, f"{backend}_selected")
        if interpret:
            registry.stats(backend).add("interpret")
            _count(metrics, "interpret")
    return KernelDecision(backend, interpret, "eligible",
                          BYTES_PER_ROW[backend])
