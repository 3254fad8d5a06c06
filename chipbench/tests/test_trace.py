"""The trace reduction, checked against hand counts: on made-up events,
and on a small trace recorded on a TPU v5e (``fixtures/``)."""

import os

import pytest

from chipbench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "ludwig_32x32x128.xplane.pb")


def test_interval_algebra():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.length(trace.union([(0, 2), (1, 3)])) == 3
    assert trace.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 6)]) == [
        (0, 2), (3, 5), (6, 10)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace.subtract([(0, 4)], []) == [(0, 4)]


def test_op_names_and_collectives():
    assert trace.op_name("fusion.12") == "fusion"
    assert trace.op_name("copy.3.1") == "copy"
    assert trace.op_name("ludwig_lb_step") == "ludwig_lb_step"
    assert trace.op_name(
        '%ludwig_lb_step.1 = (f32[19,32,32,128]{3,2,1,0:T(8,128)}) '
        'custom-call(f32[19,34,40,256]{3,2,1,0} %p), '
        'custom_call_target="tpu_custom_call"') == "ludwig_lb_step"
    assert trace.is_collective("all-reduce.1")
    assert trace.is_collective("collective-permute-done.4")
    assert not trace.is_collective("fusion.2")


def _made_up():
    """Two devices, a window 0..100 ns, hand-placed operations."""
    ops0 = [("fusion", 10, 30, False), ("kern", 25, 50, True),
            ("all-reduce", 60, 70, False), ("fusion", 65, 68, False),
            ("copy", 90, 120, False)]
    ops1 = [("kern", 0, 40, True), ("collective-permute", 40, 60, False)]
    spans = [("bench/window", 0, 100), ("bench/dispatch", 0, 20),
             ("bench/sync", 50, 95), ("bench/tick", 55, 58)]
    return {"devices": {"/device:TPU:0": ops0, "/device:TPU:1": ops1},
            "spans": spans}


def test_reduce_by_hand():
    r = trace.reduce(_made_up(), 2)
    # busy: dev0 10..50 (40) + 60..70 (10) + 90..100 (10) = 60;
    # dev1 0..60 = 60; mean 60 ns
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(60e-9)
    # XLA (non-Mosaic): dev0 10..30 + 60..70 + 90..100 = 40; dev1 20
    assert r["xla_s"] == pytest.approx(30e-9)
    # exposed collective: dev0 60..65 + 68..70 = 7; dev1 40..60 = 20
    assert r["collective_exposed_s"] == pytest.approx(13.5e-9)
    # per op, clipped to the window, mean over devices
    assert r["op_s"]["kern"] == pytest.approx((25 + 40) / 2 * 1e-9)
    assert r["op_s"]["copy"] == pytest.approx(5e-9)
    # dev0 idle gaps: 0..10 (in dispatch), 50..60 (sync; tick starts at
    # 55 and the gap's middle, 55, is in both, the inner span wins),
    # 70..90 (sync)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench/sync", pytest.approx(20e-9)]
    assert sorted(g[0] for g in gaps) == ["bench/dispatch", "bench/sync",
                                          "bench/tick"]
    assert len(r["breakdown"]["device_ops"]) == 5
    assert r["op_s"]["fusion"] == pytest.approx(23e-9 / 2)


def test_fixture_by_hand():
    """Two timesteps of Ludwig at 32x32x128 on a TPU v5e, dispatched under
    two ``bench/dispatch`` spans and blocked on under one ``bench/sync``.
    The numbers below were counted off the file directly: its 276
    operations on ``/device:TPU:0`` do not overlap, so busy is the sum of
    their durations; the six custom calls to ``tpu_custom_call`` are the
    three Pallas launches, twice."""
    data = trace.load(FIXTURE)
    spans = data["spans"]
    assert sorted(n for n, _, _ in spans) == [
        "bench/dispatch", "bench/dispatch", "bench/sync"]
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    assert (lo, hi) == (39695858.0, 42178958.0)
    data["spans"].append(("bench/window", lo, hi))
    ops = data["devices"]["/device:TPU:0"]
    assert len(ops) == 276
    r = trace.reduce(data, 1)
    assert r["window_s"] == pytest.approx(2483100e-9)
    assert r["busy_s"] == pytest.approx(1450785e-9)
    assert r["xla_s"] == pytest.approx((1450785 - 874186) * 1e-9)
    assert r["collective_exposed_s"] == 0.0
    assert r["op_s"]["ludwig_lc_update"] == pytest.approx(322383e-9)
    assert r["op_s"]["ludwig_chem_stress"] == pytest.approx(284406e-9)
    assert r["op_s"]["ludwig_lb_step"] == pytest.approx(267397e-9)
    top = [n for n, _ in r["breakdown"]["device_ops"][:3]]
    assert top == ["ludwig_lc_update", "ludwig_chem_stress", "ludwig_lb_step"]
    # idle: before the first operation (in the first dispatch), after the
    # last (in the sync), and the one gap of 8354 ns between operations
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench/sync", pytest.approx((42178958 - 41274198) * 1e-9)]
    assert gaps[1] == ["bench/dispatch",
                       pytest.approx((39814736 - 39695858) * 1e-9)]
    assert gaps[2][1] == pytest.approx(8354e-9)
