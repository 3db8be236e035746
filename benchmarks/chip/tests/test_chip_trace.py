"""The trace reduction: busy union, idle share, kernel time by name,
and idle gaps attributed to the benchmark's host spans."""
import collections
import os

import pytest

import trace_reduce as tr

E = collections.namedtuple("E", "name start_ns duration_ns")
L = collections.namedtuple("L", "name events")
P = collections.namedtuple("P", "name lines")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _planes():
    host = P("/host:CPU", [L("python", [
        E("window", 100, 1000),
        E("transmit", 100, 200),
        E("apply_round", 300, 300),
        E("offer_uploads", 800, 100),
    ])])
    dev = P("/device:TPU:0", [
        L("XLA Modules", [E("jit_apply", 300, 500)]),
        L("XLA Ops", [
            E("fusion.1", 50, 100),          # clipped to [100, 150)
            E("_fused_kernel", 400, 300),    # [400, 700)
            E("_fused_kernel", 650, 100),    # overlaps: union [400, 750)
            E("copy.2", 1050, 100),          # clipped to [1050, 1100)
        ])])
    return [host, dev, P("/device:TPU:0 SparseCore", [])]


def test_busy_union_and_idle_share():
    red = tr.reduce_trace(_planes())
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((50 + 350 + 50) * 1e-9)


def test_kernel_time_by_name():
    red = tr.reduce_trace(_planes())
    assert tr.op_seconds(red, "_fused_kernel") == pytest.approx(400e-9)
    assert tr.op_seconds(red, "no_such_kernel") is None
    assert tr.op_seconds(None, "_fused_kernel") is None


def test_idle_gaps_attributed_to_host_spans():
    red = tr.reduce_trace(_planes())
    # gaps: [150, 400) and [750, 1050); transmit covers [150, 300),
    # apply_round [300, 400), offer_uploads [800, 900).
    assert red["idle_s"]["transmit"] == pytest.approx(150e-9)
    assert red["idle_s"]["apply_round"] == pytest.approx(100e-9)
    assert red["idle_s"]["offer_uploads"] == pytest.approx(100e-9)
    assert red["idle_s"][tr.NO_SPAN] == pytest.approx(200e-9)
    assert sum(red["idle_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    bd = tr.breakdown(red)
    assert bd["device_ops"][0][0] == "_fused_kernel"
    assert bd["idle_gaps"][0][0] == tr.NO_SPAN


def test_nothing_to_read_gives_none():
    host_only = [p for p in _planes() if p.name.startswith("/host")]
    assert tr.reduce_trace(host_only) is None
    no_window = [P("/host:CPU", [L("python", [E("transmit", 0, 5)])]),
                 _planes()[1]]
    assert tr.reduce_trace(no_window) is None


def test_trace_recorded_on_the_chip():
    """Two seconds of the close cell, traced on one TPU v5e."""
    path = os.path.join(DATA, "close_2s.xplane.pb")
    red = tr.reduce_file(path)
    assert red["devices"] == 1
    assert 1.9 < red["window_s"] < 3.5
    assert 0 < red["busy_s"] <= red["window_s"]
    kernel = tr.op_seconds(red, r"^%apply_fused\.\d+ = .*custom-call\(")
    assert 0 < kernel <= red["busy_s"]
    assert sum(red["idle_s"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    top = tr.breakdown(red)["device_ops"][0][0]
    assert top.startswith("%apply_fused.") and top.endswith("custom-call")
