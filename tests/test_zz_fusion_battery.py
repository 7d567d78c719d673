"""Fused-vs-unfused TPC-DS differential battery (ISSUE 2 satellite).

Runs a representative TPC-DS subset (>= 10 queries spanning plain aggs,
multi-joins, OR-predicate blocks, subquery-as-join, windows, pivots and
count-only shapes) with ``auron.fusion.enabled`` on vs off and asserts
BIT-IDENTICAL results — fusion must only change how many XLA programs
exist, never a value. Named test_zz_* so the time-boxed tier-1 window
runs the fast fusion unit tests (test_fusion.py) first; full-suite runs
execute this battery.
"""

import tempfile

import pytest

from auron_tpu import config as cfg
from auron_tpu.frontend.session import Session
from auron_tpu.it.tpcds import generate
from auron_tpu.it.tpcds_queries import QUERIES

_SCALE = 0.02
_NAMES = ["q3", "q19", "q48", "q1", "q68", "q89",
          "q43", "q73", "q96", "q62"]


@pytest.fixture(scope="module")
def tables():
    with tempfile.TemporaryDirectory(prefix="fusion_battery_") as d:
        yield generate(d, scale=_SCALE)


def _q(name):
    return next(q for q in QUERIES if q.name == name)


@pytest.mark.parametrize("qname", _NAMES)
def test_query_bit_identical_fused_vs_unfused(qname, tables):
    conf = cfg.get_config()
    q = _q(qname)
    try:
        conf.set("auron.fusion.enabled", False)
        unfused = q.run(Session(), tables)
        conf.set("auron.fusion.enabled", True)
        fused = q.run(Session(), tables)
    finally:
        conf.unset("auron.fusion.enabled")
    assert fused.num_rows == unfused.num_rows
    assert fused.equals(unfused), \
        f"{qname}: fused result differs from unfused (values or order)"


# ---------------------------------------------------------------------------
# Fusion 2.0: the map-side combine rides the fused side of the battery
# above (its unfused side runs the unfolded partial aggregate)
# ---------------------------------------------------------------------------

def test_combine_engages_on_battery_plans(tables, monkeypatch):
    """Anti-vacuity for the battery above: the grouped-agg-over-shuffle
    queries' plans must actually STAMP the combine fold on an exchange —
    all-ineligible plans would make the differential pass trivially."""
    from auron_tpu.frontend import session as session_mod
    from auron_tpu.parallel.exchange import ShuffleExchangeOp
    planned = []
    real = session_mod.plan_from_bytes

    def spy(data, ctx=None):
        op = real(data, ctx)
        planned.append(op)
        return op

    def walk(op):
        yield op
        for c in op.children:
            yield from walk(c)

    monkeypatch.setattr(session_mod, "plan_from_bytes", spy)
    for qname in ("q62", "q96"):
        planned.clear()
        _q(qname).run(Session(), tables)
        modes = [o.combine_mode for op in planned for o in walk(op)
                 if isinstance(o, ShuffleExchangeOp)]
        assert "combine" in modes, \
            f"{qname}: no exchange of its plan is combined: {modes}"
