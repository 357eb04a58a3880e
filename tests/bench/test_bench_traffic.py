"""Each traffic mix builds and runs one tiny answer on the CPU through the
harness's own functions, and the program's answer equals the reference's
value for value."""

from __future__ import annotations

import pytest

from bench_testutil import ROOT, load_json, tiny_root

from bench import cells, compare

CELLS = [w["name"] for w in load_json(ROOT / "BENCHMARK.json")["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("name", CELLS)
def test_tiny_answer_equals_the_reference(root, name):
    cell = cells.load_cell(name, root=root)
    plan = cells.build(cell, cells.program_modules())
    seed = cells.extra_seed(2**31 + 12345, cell.traffic.get("seed_pool"))
    got = plan.run(seed)
    ref_plan = cells.build(cell, cells.reference_modules())
    want = ref_plan.run(seed)
    # the reference steps every tick; the program leaps
    assert want.events == want.ticks >= got.events
    assert compare.differing_values(got, want) == 0
    assert got.overflow == 0
    assert compare.trace_dropped(got) == 0
    expect = cells.expected(cell)
    if expect is not None:
        assert compare.closed_form_misses(got, expect) == 0
        assert got.ticks < cells.DRAIN_MAX_TICKS
    else:
        assert got.ticks == cells.horizon_ticks(cell)
    if cell.traffic.get("arrivals"):
        assert got.arrivals_injected > 0 and got.requests_done > 0
        ar = ref_plan.kwargs["arrivals"]
        offered = compare.offered_requests(
            ar, seed, ref_plan.cfg.arrival_gap_q8,
            ref_plan.cfg.arrival_batch, got.ticks)
        assert compare.request_ledger(got, offered, int(ar.task_cost)) == {
            "requests_unbalanced": 0, "requests_not_once": 0,
            "sojourn_misses": 0}


def test_the_warm_call_runs_the_same_shapes_and_almost_nothing(root):
    cell = cells.load_cell("starlink-s1.orbit-open", root=root)
    plan = cells.build(cell, cells.program_modules())
    r = plan.run(3, plan.dead_kwargs())
    assert r.nodes <= 1 and r.busy_ticks == 0
