"""The trace reduction, checked on a small trace recorded on a TPU v5e (the
first ops of a `starlink-s1.fib-drain` run with `--trace 1`, in the plain
form of `trace_reduce.load`) and on hand-made ones."""

from __future__ import annotations

import json

import pytest

from bench_testutil import ROOT

from bench import trace_reduce as tr

RECORDED = ROOT / "tests" / "bench" / "trace_small.json"


def _sweep_busy(ops, w0, w1):
    """Busy time by an overlap counter: +1 at each start, -1 at each end."""
    marks = []
    for _, s, d in ops:
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            marks += [(s, 1), (e, -1)]
    marks.sort(key=lambda m: (m[0], -m[1]))
    busy, depth, since = 0, 0, None
    for t, step in marks:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_op_names_from_hlo_text():
    k = "%steal_compact.7 = (s32[1792,32]{1,0:T(8,128)S(1)}) custom-call(%x)"
    assert tr.op_name(k) == "steal_compact.7"
    assert tr.op_base(tr.op_name(k)) == "steal_compact"
    f = "%fusion.751 = s32[1584]{0} fusion(s32[1584,2]{1,0} %steal_compact.7)"
    assert tr.op_base(tr.op_name(f)) == "fusion"
    assert tr.op_base("deque_apply") == "deque_apply"
    assert tr.op_base("while.425") == "while"


def test_hand_made_trace():
    norm = {"devices": {"/device:TPU:0": [["fusion.1", 0, 10],
                                          ["steal_compact.3", 5, 10],
                                          ["deque_apply.2", 30, 5],
                                          ["fusion.2", 50, 50]]},
            "host": [["bench.answer", 0, 100], ["bench.fetch", 40, 8]],
            "window_ns": [0, 100]}
    out = tr.reduce(norm, kernels=("steal_compact", "deque_apply"))
    assert out["busy_s"] == pytest.approx(70e-9)      # [0,15] [30,35] [50,100]
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["kernels"]["steal_compact"] == {"count": 1,
                                               "device_s": pytest.approx(1e-8)}
    assert out["kernels"]["deque_apply"]["count"] == 1
    assert out["breakdown"]["device_ops"][0] == ["fusion.2",
                                                 pytest.approx(5e-8)]
    # gap [15, 30] lies in bench.answer only; [35, 50] also in bench.fetch
    gaps = sorted(out["breakdown"]["idle_gaps"])
    assert gaps == [["bench.answer", pytest.approx(15e-9)],
                    ["bench.fetch", pytest.approx(15e-9)]]


def test_two_chips_average_their_busy_time():
    norm = {"devices": {"/device:TPU:0": [["a.1", 0, 40]],
                        "/device:TPU:1": [["a.1", 0, 20], ["b.1", 60, 20]]},
            "host": [], "window_ns": [0, 100]}
    out = tr.reduce(norm, default_span="bench.answer")
    assert out["busy_s"] == pytest.approx(40e-9)
    assert out["breakdown"]["idle_gaps"] == [["bench.answer",
                                              pytest.approx(40e-9)]]


def test_recorded_trace():
    norm = json.loads(RECORDED.read_text())
    (plane, ops), = norm["devices"].items()
    assert plane.startswith("/device:TPU:")
    w0, w1 = norm["window_ns"]
    out = tr.reduce(norm, kernels=("steal_compact", "deque_apply"),
                    default_span="bench.answer")
    assert out["busy_s"] == pytest.approx(_sweep_busy(ops, w0, w1) / 1e9)
    assert 0 < out["busy_s"] <= out["window_s"] == pytest.approx(
        (w1 - w0) / 1e9)
    for k in ("steal_compact", "deque_apply"):
        mine = [d for n, _, d in ops if n.split(".")[0] == k]
        assert mine, k
        assert out["kernels"][k]["count"] == len(mine)
        assert out["kernels"][k]["device_s"] == pytest.approx(sum(mine) / 1e9)
    assert not any(tr.op_base(n) in tr.CONTROL_OPS for n, _, _ in ops)
    assert len(out["breakdown"]["device_ops"]) == tr.TOP
    assert all(name == "bench.answer"
               for name, _ in out["breakdown"]["idle_gaps"])
