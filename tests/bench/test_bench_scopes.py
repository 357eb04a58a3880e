"""The program's layer names as the benchmark reads them: device scopes
per op, the `simulate` spans and their step counters, checked on a small
hand-made trace (`trace_scopes.json`, in the plain forms of
`program_trace.with_paths` and `load_spans`), on the program compiled at a
tiny size, and through a whole traced run of the harness on the CPU."""

from __future__ import annotations

import dataclasses
import json
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testutil import ROOT, tiny_root

from bench import cells, harness, program_trace as pt

FIXTURE = json.loads((ROOT / "tests" / "bench" / "trace_scopes.json")
                     .read_text())
NEW_METRICS = ("tick.device_share", "linkstate.device_share",
               "preshed.device_share", "arrivals.device_share",
               "grant.device_share", "commit.device_share",
               "recorder.device_share", "leap.device_share",
               "famine.device_share", "device.unscoped_share",
               "famine.useful_share", "famine.tick_share",
               "leap.tick_share", "simulate.host_share")
HOST_METRICS = NEW_METRICS[-4:]


@pytest.mark.parametrize("path,scope", [
    ("jit(_sim_core)/while/body/stepper.tick/tick.commit/add", "tick.commit"),
    ("jit(_sim_core)/while/body/stepper.tick/mul", "stepper.tick"),
    ("jit(f)/vmap()/while/body/stepper.famine/cond/branch_1_fun/while/"
     "body/stepper.tick/gather", "stepper.tick"),
    ("jit(_sim_core)/while/cond/lt", None),
    ("", None),
])
def test_innermost_scope_wins(path, scope):
    assert pt.scope_of(path) == scope


def test_op_paths_from_the_compiled_text():
    """The op paths come from the program's compiled HLO text: a fusion
    takes the scope its own metadata names, which is its root's."""
    @jax.jit
    def f(x):
        with jax.named_scope("stepper.tick"):
            y = jnp.sin(x) * 2
            with jax.named_scope("tick.commit"):
                return jnp.cos(y) + 1

    paths = pt.hlo_op_paths(f.lower(jnp.ones(8)).compile().as_text())
    fusions = [name for name in paths if "fusion" in name]
    assert fusions
    assert {pt.scope_of(paths[name]) for name in fusions} == {"tick.commit"}
    assert {"stepper.tick", "tick.commit"} <= {
        pt.scope_of(p) for p in paths.values()}


HLO_TEXT = """\
HloModule jit__sim_core, is_scheduled=true

%fused_computation.28 (param_0.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  ROOT %gather.3 = s32[8]{0} gather(%param_0.1), metadata={op_name="jit(_sim_core)/while/body/stepper.tick/tick.grant/gather"}
}

%fused_computation.815 (param_0.2: s32[8]) -> s32[8] {
  %param_0.2 = s32[8]{0} parameter(0)
  ROOT %fusion.684 = s32[8]{0} fusion(%param_0.2), kind=kCustom, calls=%fused_computation.28
}

%branch_1 (param_0.3: s32[8]) -> s32[8] {
  %param_0.3 = s32[8]{0} parameter(0)
  ROOT %copy.12 = s32[8]{0} copy(%param_0.3)
}

%branch_0 (param_0.4: s32[8]) -> s32[8] {
  ROOT %param_0.4 = s32[8]{0} parameter(0)
}

%region_1.168.sunk (p.5: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p.5 = (s32[], s32[8]{0}) parameter(0)
  %get-tuple-element.1 = s32[8]{0} get-tuple-element(%p.5), index=1
  %copy.389 = s32[8]{0} copy(%get-tuple-element.1)
  %fusion.697 = s32[8]{0} fusion(%copy.389), kind=kCustom, calls=%fused_computation.815, backend_config={"used_scoped_memory_configs":[]}
  %i.1 = s32[] get-tuple-element(%p.5), index=0
  %conditional.2 = s32[8]{0} conditional(%i.1, %fusion.697, %fusion.697), branch_computations={%branch_0, %branch_1}, metadata={op_name="jit(_sim_core)/while/body/stepper.famine/cond"}
  ROOT %tuple.1 = (s32[], s32[8]{0}) tuple(%i.1, %conditional.2)
}

ENTRY %main.9 (x.1: (s32[], s32[8])) -> (s32[], s32[8]) {
  %x.1 = (s32[], s32[8]{0}) parameter(0)
  ROOT %while.1 = (s32[], s32[8]{0}) while(%x.1), condition=%region_1.168.sunk, body=%region_1.168.sunk, metadata={op_name="jit(_sim_core)/while"}
}
"""


@pytest.mark.parametrize("op,scope", [
    ("gather.3", "tick.grant"),
    ("fusion.684", "tick.grant"),
    ("fusion.697", "tick.grant"),   # fusions nested, no metadata of its own
    ("copy.12", "stepper.famine"),  # inside a branch of the famine `cond`
    ("copy.389", None),             # the loop body's own copy of its carry
    ("while.1", None),
])
def test_ops_without_metadata_take_their_enclosing_scope(op, scope):
    """Ops the compiler makes carry no metadata of their own: a fusion is
    named by its fused root, any other op by the op that calls its
    computation."""
    paths = pt.hlo_op_paths(HLO_TEXT)
    assert pt.scope_of(paths[op]) == scope


@pytest.mark.parametrize("op_paths,want", [
    (FIXTURE["paths"], "shares"),
    ({"fusion.1": "jit(f)/while/body/x"}, RuntimeError),
    (None, None),
], ids=["scoped", "no-op-maps", "program-without-scopes"])
def test_window_scopes_or_an_error(monkeypatch, op_paths, want):
    """The window's TPU ops with the compiled program's paths: shares when
    ops map to scopes, an error when the program names scopes and none of
    the trace's ops maps to one, nothing from a program that names none."""
    monkeypatch.setattr(pt, "newest_xplane", lambda d: d / "t.xplane.pb")
    monkeypatch.setattr(pt, "device_ops", lambda path: FIXTURE["devices"])
    monkeypatch.setattr(pt, "program_op_paths", lambda plan: op_paths)
    run = SimpleNamespace(program_trace={"plan": None})
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="maps to a scope"):
            pt._device_trace_scopes(run)
    elif want is None:
        assert pt._device_trace_scopes(run) is None
    else:
        assert pt._device_trace_scopes(run) == pt.device_scopes(FIXTURE)


def test_scope_shares_sum_to_100():
    seconds = pt.device_scopes(FIXTURE)
    # ns: recorder 3000, tick 4000, grant 1000, famine 2 x 2500, leap
    # 1500, commit 500, preshed 1000, unscoped 500 + 500, of 17000
    assert seconds["stepper.famine"] == pytest.approx(5e-6)
    shares = pt.scope_shares(seconds)
    assert set(shares) == set(pt.SCOPES) | {pt.UNSCOPED}
    assert sum(shares.values()) == pytest.approx(100.0)
    want = {"tick.recorder": 3000, "stepper.tick": 4000, "tick.grant": 1000,
            "stepper.famine": 5000, "stepper.leap": 1500, "tick.commit": 500,
            "tick.preshed": 1000, pt.UNSCOPED: 1000, "tick.arrivals": 0,
            "tick.linkstate": 0}
    for k, ns in want.items():
        assert shares[k] == pytest.approx(100.0 * ns / 17000), k


def test_two_chips_average_their_scope_time():
    norm = {"devices": {"/device:TPU:0": [["a.1", 40]],
                        "/device:TPU:1": [["a.1", 20], ["b.1", 20]]},
            "paths": {"a.1": "while/body/stepper.leap/x", "b.1": "x"}}
    assert pt.device_scopes(norm) == {"stepper.leap": pytest.approx(30e-9),
                                      pt.UNSCOPED: pytest.approx(10e-9)}


def test_host_spans_give_host_share_and_counters():
    summary = pt.host_summary(FIXTURE["spans"])
    assert summary["simulate_s"] == pytest.approx(1e-4)
    assert set(summary["children_s"]) == set(pt.CHILD_SPANS)
    m = pt.counter_shares(summary, famine_batch=64)
    assert m == {"simulate.host_share": pytest.approx(20.0),
                 "leap.tick_share": pytest.approx(100 * 250 / 700),
                 "famine.tick_share": pytest.approx(50.0),
                 "famine.useful_share": pytest.approx(100 * 350 / 640)}
    c = summary["counters"]
    assert (m["leap.tick_share"] + m["famine.tick_share"]
            + 100 * c["iterations"] / c["ticks"]) == pytest.approx(100.0)


def test_a_sweep_root_span_reads_as_simulate():
    """`simulate_sweep` writes the same children under its own root span,
    with the counters summed over its points."""
    spans = [[("simulate_sweep" if s[0] == "simulate" else s[0]), *s[1:]]
             for s in FIXTURE["spans"]]
    assert any(s[0] == "simulate_sweep" for s in spans)
    assert pt.host_summary(spans) == pt.host_summary(FIXTURE["spans"])


def test_metrics_absent_without_scope_span_or_replay():
    no_scope = {"devices": FIXTURE["devices"],
                "paths": {k: "jit(f)/while/body/x" for k in FIXTURE["paths"]}}
    assert pt.device_scopes(no_scope) is None
    assert pt.scope_shares(None) == {}
    spans = FIXTURE["spans"]
    assert pt.host_summary([s for s in spans if s[0] != "simulate"]) is None
    assert pt.counter_shares(None, 64) == {}
    # a parent program's `simulate` span carries no counters
    bare = [["simulate", 0, 10, {}]]
    assert pt.host_summary(bare) is None
    root, *rest = spans
    no_replay = [[root[0], root[1], root[2],
                  {**root[3], "famine_ticks": 0, "famine_replays": 0}]] + rest
    m = pt.counter_shares(pt.host_summary(no_replay), 64)
    assert "famine.useful_share" not in m and m["famine.tick_share"] == 0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_reports_nothing_without_a_trace(name):
    run = SimpleNamespace(trace=None, answers=[])
    assert harness.metric_reader(name)(run) is None


def _compiled_scopes(cfg, **kw):
    """Every `stepper.*`/`tick.*` component of the compiled loop's
    `op_name` metadata."""
    from repro.core import simulator, tasks, topology
    mesh = topology.MeshTopology.square(9)
    job = tasks.FibWorkload(n=12, cutoff=6, max_leaf_cost=8)
    text = simulator.lower_simulate(job, mesh, cfg, **kw).compile().as_text()
    return {part for path in pt.hlo_op_paths(text).values()
            for part in path.split("/")
            if re.fullmatch(r"(stepper|tick)\.\w+", part)}


def _dynamic_kwargs():
    from repro.core import arrivals, linkstate, topology
    mesh = topology.MeshTopology.square(9)
    W = mesh.num_workers
    starts = np.asarray([0, 20, 45], np.int32)
    E = len(starts)
    tau = np.full((E, W, 4), 2, np.int32)
    up = np.ones((E, W, 4), bool)
    up[1, 4] = False
    for d in range(4):
        n = mesh.neighbor_table[4, d]
        if n >= 0:
            up[1, n, linkstate.OPPOSITE[d]] = False
    ls = linkstate.LinkStateSchedule(starts, tau, up,
                                     np.ones((E, W), np.int32)).validate(mesh)
    ft = -np.ones(W, np.int32)
    ft[4] = 20
    return dict(linkstate=ls, fail_time=ft,
                arrivals=arrivals.ArrivalConfig(task_cost=3, num_stations=2))


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
def test_program_names_the_benchmarks_scopes(dynamic):
    """The staged TPU path compiled for the CPU names every scope the
    benchmark reads that its configuration runs, and no other."""
    from repro.core import simulator, stealing, tracing
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              capacity=32, max_ticks=100,
                              deque_backend="staged")
    kw = {}
    want = {"stepper.tick", "tick.grant", "tick.commit", "stepper.leap",
            "stepper.famine"}
    if dynamic:
        cfg = dataclasses.replace(
            cfg, preshed=True, warn_ticks=4, arrival_gap_q8=512,
            trace=tracing.TraceConfig(ring_capacity=256, bins=4,
                                      bin_ticks=32))
        kw = _dynamic_kwargs()
        want = set(pt.SCOPES)
    assert _compiled_scopes(cfg, **kw) == want


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell_name", ["starlink-s1.fib-drain",
                                       "starlink-s1.orbit-open"])
def test_traced_run_reports_host_metrics(root, cell_name):
    """A whole `--trace 1` run on the CPU: the host-only profile of the
    window's first answer gives the counter and span metrics, exact to
    the answer's own ticks and iterations; the scope shares, which need a
    TPU plane, are absent."""
    cell = cells.load_cell(cell_name, root=root)
    args = SimpleNamespace(workload=cell_name, seed=2**31 + 7, seconds=0.0,
                           trace=1, control=False)
    out = harness.run_cell(cell, args, jax.devices(), clock0=0.0, root=root)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"famine.tick_share", "leap.tick_share",
            "simulate.host_share"} <= set(m)
    assert not set(NEW_METRICS[:10]) & set(m)
    assert 0 < m["simulate.host_share"] < 100
    if "famine.useful_share" in m:
        assert 0 < m["famine.useful_share"] <= 100
    # the profiled answer is the window's first: its ticks and iterations
    plan = cells.build(cell, cells.program_modules())
    first = plan.run(plan.seed(args.seed, 0))
    assert (m["leap.tick_share"] + m["famine.tick_share"]
            + 100 * first.events / first.ticks) == pytest.approx(100.0)
