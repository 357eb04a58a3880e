"""A whole run of the harness, past its look for a chip, at a tiny size on
the CPU: sound, it comes out correct; with the timed path broken
underneath, or under the control, it comes out not correct."""

from __future__ import annotations

from types import SimpleNamespace

import jax
import pytest

from bench_testutil import tiny_root

from bench import cells, harness


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


def _run(root, cell_name, entry=None, control=False, seed=2**31 + 99):
    cell = cells.load_cell(cell_name, root=root)
    args = SimpleNamespace(workload=cell_name, seed=seed, seconds=0.0,
                           trace=0, control=control)
    return harness.run_cell(cell, args, jax.devices(), clock0=0.0,
                            root=root, entry=entry)


def _state_unchanged(plan, seed):
    """A step that returns its state unchanged: no tick ever runs."""
    return plan.run(seed)._replace(ticks=0, nodes=0, events=0, result=0,
                                   busy_ticks=0)


def _altered(plan, seed):
    """An answer altered where it is produced."""
    r = plan.run(seed)
    return r._replace(attempts=r.attempts + 1)


def _request_lost(plan, seed):
    """A completed request dropped from the ledger where it is produced:
    the counters and the percentiles agree with each other, the ring still
    holds it."""
    r = plan.run(seed)
    return r._replace(requests_done=r.requests_done - 1)


def _extra_altered(plan, seed):
    """Only the answer made after the window altered."""
    r = plan.run(seed)
    if seed < plan.cell.traffic.get("seed_pool", 0):
        return r
    return r._replace(per_worker_busy=r.per_worker_busy + 1)


def test_sound_run_is_correct(root):
    out = _run(root, "starlink-s1.fib-drain")
    assert out["correct"], out["checks"]
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    # the CPU reports no allocator peak, so peak_hbm_mib is left out
    assert set(out["metrics"]) == {"setup_s", "sim_ticks_per_s"}


@pytest.mark.parametrize("cell,fault", [
    ("starlink-s1.fib-drain", _state_unchanged),
    ("starlink-s1.fib-drain", _altered),
    ("starlink-s1.fib-drain", _extra_altered),
    ("starlink-s1.orbit-open", _altered),
    ("starlink-s1.orbit-open", _request_lost),
])
def test_broken_timed_path_is_not_correct(root, cell, fault):
    out = _run(root, cell, entry=fault)
    assert not out["correct"], out["checks"]
    assert out["failed"] >= 1


@pytest.mark.parametrize("cell", ["starlink-s1.fib-drain",
                                  "starlink-s1.orbit-open"])
def test_control_is_not_correct(root, cell):
    """The control: the program's draws from non-partitionable threefry,
    which breaks the seed-reproducibility guarantee."""
    try:
        out = _run(root, cell, control=True)
    finally:
        jax.config.update("jax_threefry_partitionable", True)
    assert not out["correct"], out["checks"]
    assert out["checks"]["ref_diff_values"]["value"] > 0


def test_sound_open_loop_run_is_correct(root):
    out = _run(root, "starlink-s1.orbit-open")
    assert out["correct"], out["checks"]
    for k in ("requests_unbalanced", "requests_not_once", "sojourn_misses",
              "ref_diff_values"):
        assert out["checks"][k] == {"value": 0, "limit": 0}


def test_a_lost_request_fails_the_ledger_on_every_answer(root):
    out = _run(root, "starlink-s1.orbit-open", entry=_request_lost)
    assert out["checks"]["requests_unbalanced"]["value"] >= out["attempted"]
    assert out["failed"] == out["attempted"]
