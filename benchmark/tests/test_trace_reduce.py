"""The reduction from a trace to busy time, top operations and idle
gaps: on planes written by hand, where every number is known, and on a
small trace recorded on the v5e (PR 24)."""

import glob
import os

import pytest

from conftest import BENCH
from harness import trace_reduce as tr

MS = 1_000_000


def test_by_hand():
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [(0, 30 * MS, "jit_f")]),
            ("XLA Ops", [(0, 10 * MS, "fusion.1"), (5 * MS, 20 * MS, "copy"),
                         (60 * MS, 70 * MS, "fusion.1")])]),
        ("/device:TPU:1", [("XLA Ops", [(90 * MS, 100 * MS, "all-to-all.3")])]),
        ("/host:CPU", [("python3", [(0, 100 * MS, "outer"),
                                    (22 * MS, 58 * MS, "PjitFunction(add)"),
                                    (71 * MS, 99 * MS, "TransferToDevice")])]),
    ]
    out = tr.reduce_planes(planes)
    assert out["devices"] == 2 and out["modules"] == 1
    assert out["window_s"] == pytest.approx(0.100)
    # chip 0: [0,20) and [60,70) = 30 ms; chip 1: 10 ms; mean 20 ms
    assert out["busy_s"] == pytest.approx(0.020)
    assert out["busy_s_by_device"]["/device:TPU:0"] == pytest.approx(0.030)
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.010)      # 20 ms over 2 chips
    assert ops["all-to-all.3"] == pytest.approx(0.005)
    gaps = dict(out["idle_gaps"])
    # chip 0 idles 20-60 under add and 70-100 under the transfer; chip 1
    # idles 0-90, most of it under `outer` alone
    assert gaps["PjitFunction(add)"] == pytest.approx(0.020)
    assert gaps["TransferToDevice"] == pytest.approx(0.015)
    assert gaps["outer"] == pytest.approx(0.045)
    assert tr.merge([(0, 5), (3, 4), (5, 9), (12, 13)]) == [(0, 9), (12, 13)]
    assert tr.short_name(
        "%fusion.15 = u32[65536]{0:T(1024)S(1)} fusion(u32[4096]{0:T(1024)} "
        "%x), kind=kCustom") == "%fusion.15 fusion u32[65536]"
    assert tr.short_name(
        "%while.2 = (s32[]{:T(128)}, pred[8]{0:T(8)}) while((s32[]) %t), "
        "condition=%c") == "%while.2 while (tuple)"


def test_a_chip_with_no_plane_still_counts():
    planes = [("/device:TPU:0", [("XLA Ops", [(0, 40 * MS, "fusion")])]),
              ("/host:CPU", [("python3", [(0, 100 * MS, "outer")])])]
    assert tr.reduce_planes(planes)["busy_s"] == pytest.approx(0.040)
    assert tr.reduce_planes(planes, chips=4)["busy_s"] == pytest.approx(0.010)


def test_no_events():
    out = tr.reduce_planes([("/host:CPU", [])])
    assert out["busy_s"] == 0.0 and out["window_s"] == 0.0


def test_recorded_on_the_chip():
    found = glob.glob(os.path.join(BENCH, "tests", "data", "*.xplane.pb"))
    assert found, "the recorded v5e trace is missing"
    out = tr.reduce_file(found[0])
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    assert sum(s for _n, s in out["device_ops"]) <= out["busy_s"] * 1.0001 \
        or len(out["op_s"]) > 10
    assert out["idle_gaps"]
