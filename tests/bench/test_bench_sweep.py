"""A sweep answer made from data alone: a UTS job on a ragged square mesh
through `simulate_sweep`, the four strategies × τ ∈ {1, 5}, built by the
one generator from a configuration and a traffic file
(`sweep-square10.json`, `uts-grid-small.json`) and driven through the
whole harness on the CPU. Sound, it equals the reference's tick-stepped
sweep point by point and comes out correct; with the timed path broken
underneath, or under the control, it comes out not correct."""

from __future__ import annotations

import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench_testutil import ROOT, load_json

from bench import cells, compare, harness, peaks

HERE = Path(__file__).resolve().parent
CELL = "square10.uts-small"
SEED = 2**31 + 4321
STRATEGIES = ("global", "neighbor", "lifeline", "adaptive")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose `BENCHMARK.json` holds one more cell, made of the
    two data files and its entries alone."""
    root = tmp_path_factory.mktemp("sweep") / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    conf = load_json(HERE / "sweep-square10.json")
    shutil.copy(HERE / "sweep-square10.json",
                root / "bench" / "configs" / "square10.json")
    shutil.copy(HERE / "uts-grid-small.json",
                root / "bench" / "traffic" / "uts-small.json")
    bench = load_json(ROOT / "BENCHMARK.json")
    bench["configs"].append({"name": "square10", "source": conf["source"],
                             "file": "bench/configs/square10.json",
                             "reduced": [], "why": "test data"})
    bench["workloads"].append({"name": CELL, "config": "square10",
                               "traffic": "uts-small", "chips": 1,
                               "why": "test data"})
    for m in bench["per_layer"]:
        if m["name"] != "setup.tables_s":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def cell(root):
    return cells.load_cell(CELL, root=root)


@pytest.fixture(scope="module")
def program(cell):
    return cells.build(cell, cells.program_modules())


@pytest.fixture(scope="module")
def sweeps(cell, program):
    """The program's and the reference's answer of one seed."""
    seed = program.seed(SEED, 0)
    ref = cells.build(cell, cells.reference_modules())
    return seed, program.run(seed), ref.run(seed)


def _run(root, entry=None, control=False, devices=None, trace=0):
    cell = cells.load_cell(CELL, root=root)
    args = SimpleNamespace(workload=CELL, seed=SEED, seconds=0.0,
                           trace=trace, control=control)
    return harness.run_cell(cell, args, devices or jax.devices(),
                            clock0=0.0, root=root, entry=entry)


def test_the_grid_and_the_mesh_come_from_data(cell, program):
    ref = cells.build(cell, cells.reference_modules())
    for plan in (program, ref):
        assert [(s.value, t) for s, t in plan.points] == [
            (s, t) for s in STRATEGIES for t in (1, 5)]
        m = plan.mesh
        assert (m.num_workers, m.rows, m.cols, m.torus) == (10, 3, 4, False)
        assert plan.job.d_max == 6 and plan.job.root_seed == 19
    cfgs = program.point_configs(11)
    assert {c.seed for c in cfgs} == {11}
    assert len({c.static for c in cfgs}) == 1


def test_program_sweep_equals_the_reference_sweep(cell, sweeps):
    seed, got, want = sweeps
    expect = cells.expected(cell)
    assert len(got) == len(want) == len(expect) == 8
    for g, w, e in zip(got, want, expect):
        assert compare.differing_values(g, w) == 0
        assert w.events == w.ticks          # the reference steps every tick
        assert (g.result, g.nodes) == (e["result"], e["nodes"])
        assert g.overflow == 0 and g.ticks < cells.DRAIN_MAX_TICKS
    assert compare.differing_values(got, want) == 0
    # the points run different schedules of one tree
    assert len({g.ticks for g in got}) > 1


def test_the_closed_form_counts_the_programs_tree():
    from repro.core import tasks as ptasks

    from bench.reference import tasks as rtasks
    for d in (6, 10):
        ref = rtasks.UtsWorkload(b0=4.0, d_max=d, root_seed=19)
        assert ref.expected_nodes() == \
            ptasks.UtsWorkload(b0=4.0, d_max=d, root_seed=19).count_tree()
        assert ref.expected_result() == ref.expected_nodes()


def test_every_point_takes_the_answers_seed(cell, program, sweeps):
    seed, got, _ = sweeps
    one = program.point_configs(seed)[5]
    alone = program.m.simulator.simulate(program.job, program.mesh, one,
                                         **program.kwargs)
    assert compare.differing_values(alone, got[5]) == 0


def test_answer_counts_aggregate_a_sweep(program, sweeps):
    seed, got, _ = sweeps
    ans = harness.timed_answer(program, seed, lambda p, s: got)
    assert ans.ticks == sum(r.ticks for r in got)
    assert ans.events == max(r.events for r in got) < ans.ticks
    assert harness.answer_counts(got[0]) == (got[0].ticks, got[0].events)


def test_kernel_shapes_count_the_points_of_one_call(program):
    shapes = harness.kernel_shapes(program)
    assert shapes["points"] == 8
    one = dict(shapes, points=1)
    assert peaks.deque_apply_bytes(shapes["workers"], shapes["lanes"],
                                   shapes["width"], shapes["points"]) == \
        8 * peaks.deque_apply_bytes(one["workers"], one["lanes"],
                                    one["width"])


def test_the_sweep_runs_on_the_cells_chips_only(root, monkeypatch):
    """A one-chip cell handed a host of several devices sweeps on the
    first alone, in the warm call, the window and the extra answer."""
    from repro.core import simulator
    seen = []
    sweep = simulator.simulate_sweep

    def spy(*a, devices=None, **kw):
        seen.append(list(devices))
        return sweep(*a, devices=devices, **kw)

    monkeypatch.setattr(simulator, "simulate_sweep", spy)
    host = jax.devices() * 4
    out = _run(root, devices=host)
    assert out["correct"], out["checks"]
    assert len(seen) == 3
    assert all(d == host[:1] for d in seen)


def test_sound_sweep_run_is_correct(root):
    out = _run(root)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 2
    assert set(out["checks"]) == {
        "closed_form_misses", "undrained", "overflow_tasks",
        "trace_dropped", "points_missing", "ref_diff_values"}
    assert all(v == {"value": 0, "limit": 0} for v in out["checks"].values())
    assert set(out["metrics"]) == {"setup_s", "sim_ticks_per_s"}


def _swapped(plan, seed):
    """Two points' strategies swapped where the answer is produced: the
    GLOBAL and NEIGHBOR points at τ = 1 trade places."""
    r = plan.run(seed)
    r[0], r[2] = r[2], r[0]
    return r


def _half_left_out(plan, seed):
    """Half of the grid's points left out."""
    return plan.run(seed)[::2]


def _state_unchanged(plan, seed):
    """A step that returns its state unchanged: no tick runs on any
    point."""
    return [r._replace(ticks=0, nodes=0, events=0, result=0, busy_ticks=0)
            for r in plan.run(seed)]


def _one_point_altered(plan, seed):
    r = plan.run(seed)
    r[7] = r[7]._replace(attempts=r[7].attempts + 1)
    return r


@pytest.mark.parametrize("fault,check", [
    (_swapped, "ref_diff_values"),
    (_half_left_out, "points_missing"),
    (_state_unchanged, "closed_form_misses"),
    (_one_point_altered, "ref_diff_values"),
])
def test_broken_sweep_is_not_correct(root, fault, check):
    out = _run(root, entry=fault)
    assert not out["correct"], out["checks"]
    assert out["checks"][check]["value"] > 0
    assert out["failed"] >= 1


def test_control_sweep_is_not_correct(root):
    """The control: the program's draws from non-partitionable threefry."""
    try:
        out = _run(root, control=True)
    finally:
        jax.config.update("jax_threefry_partitionable", True)
    assert not out["correct"], out["checks"]
    assert out["checks"]["ref_diff_values"]["value"] > 0


def test_traced_sweep_run_reads_the_sweep_span(root, cell, tmp_path,
                                               monkeypatch):
    """`--trace 1` on the CPU: the host-only profile's `simulate_sweep`
    span gives the counter and span metrics, its counters summed over the
    first answer's points. The profiles go to a directory of the test's
    own, so that a traced run of another test file can run beside it."""
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path / "out")
    out = _run(root, trace=1)
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert {"famine.tick_share", "leap.tick_share",
            "simulate.host_share"} <= set(m)
    assert 0 < m["simulate.host_share"] < 100
    plan = cells.build(cell, cells.program_modules())
    first = plan.run(plan.seed(SEED, 0))
    ticks = sum(r.ticks for r in first)
    iterations = sum(r.events for r in first)
    assert (m["leap.tick_share"] + m["famine.tick_share"]
            + 100 * iterations / ticks) == pytest.approx(100.0)
