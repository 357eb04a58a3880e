"""The benchmark's definition: `BENCHMARK.json` against its own rules, the
files it names, and the arithmetic of its metrics, checked by hand counts.
No JAX device is touched and no topology is described."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_testutil import ROOT, load_json

from bench import cells, harness, peaks

BENCH = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench", "tests/bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


@pytest.mark.parametrize("kind,names", [
    ("configs", [c["name"] for c in BENCH["configs"]]),
    ("workloads", CELLS),
    ("metrics", [m["name"] for m in METRICS]),
])
def test_names_keep_to_the_character_rules(kind, names):
    assert names and len(set(names)) == len(names), kind
    for n in names:
        assert NAME.match(n), (kind, n)


def test_units_and_directions():
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m


def test_end_to_end_metrics():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}, m
        assert m["source"] in ("host_clock", "device_trace"), m
        assert 0.01 <= m["bound"] <= 0.25, m
    assert next(m for m in BENCH["end_to_end"]
                if m["name"] == "setup_s")["bound"] <= 0.25


def test_per_layer_metrics():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}, m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock"), m
        assert m["moves"] in e2e, m
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"], m
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        for w in m.get("workloads", []):
            assert w in CELLS, (m["name"], w)
    assert all(len(v) == 1 for v in layers.values()), layers


def test_roofline_metrics_are_named_for_their_kernel():
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%", m


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        e2e = [m["name"] for m in harness.cell_metrics(BENCH, cell, False)]
        layer = harness.cell_metrics(BENCH, cell, True)
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert layer, cell


def test_workloads_and_configs():
    used = set()
    pairs = set()
    four = 0
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"], w
        assert NAME.match(w["traffic"]), w
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert c["name"] in used, c["name"]
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not re.search(r"(_dim|_rank)$|hidden|intermediate|"
                                 r"width|head", k), k


@pytest.mark.parametrize("cell", CELLS)
def test_every_entry_resolves_to_its_files(cell):
    c = cells.load_cell(cell)
    conf = c.config
    for key in ("source", "assumed", "reduced", "guarantees", "mesh",
                "capacity", "hop_ticks"):
        assert key in conf, (cell, key)
    entry = next(e for e in BENCH["configs"] if e["name"] == c.config_name)
    assert conf["source"] == entry["source"]
    assert conf["reduced"] == entry["reduced"]
    assert c.traffic["answer"] == "simulate"
    for spec in (harness.cell_metrics(BENCH, cell, False)
                 + harness.cell_metrics(BENCH, cell, True)):
        assert callable(harness.metric_reader(spec["name"])), spec


def test_a_new_traffic_file_and_entry_resolve_with_no_edit(tmp_path):
    """A later change adds a cell by adding a data file and an entry."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    tr = load_json(ROOT / "bench" / "traffic" / "fib-drain.json")
    tr["job"]["max_leaf_cost"] = 192
    (root / "bench" / "traffic" / "fib-fine.json").write_text(json.dumps(tr))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "starlink-s1.fib-fine",
                               "config": "starlink-s1",
                               "traffic": "fib-fine", "chips": 1,
                               "why": "dummy"})
    for m in bench["per_layer"]:
        if "setup.tables_s" != m["name"]:
            m["workloads"].append("starlink-s1.fib-fine")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load_cell("starlink-s1.fib-fine", root=root)
    assert cell.traffic["job"]["max_leaf_cost"] == 192
    assert cell.config["mesh"]["rows"] == 72
    layer = [m["name"] for m in harness.cell_metrics(bench, cell.name, True)]
    assert "stepper.ticks_per_event" in layer and "setup.tables_s" not in layer
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_peaks_table_and_unknown_device():
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_kernel_bytes_by_hand():
    # steal_compact: 8 ranks x 4 fields exported per worker, plus 5 cursor
    # ints (bottom, size, grants in; bottom, size out): 37 int32 = 148 B.
    assert peaks.steal_compact_bytes(1584, 8, 4) == 1584 * 148
    # deque_apply: 9 lanes x 4 fields, 9 slots, 1 count: 46 int32 = 184 B.
    assert peaks.deque_apply_bytes(1584, 9, 4) == 1584 * 184
    # 819 MB in 2 ms of kernel time: half of 819 GB/s, 50%.
    assert peaks.roofline_share(819e6, 2e-3, "TPU v5 lite") == \
        pytest.approx(50.0)
    assert peaks.roofline_share(1e6, 0.0, "TPU v5 lite") is None


def _shapes(points, chips=1):
    """`kernel_shapes` of a W=1584 plan: `simulate` (points None) or a
    sweep of `points` on `chips`."""
    plan = SimpleNamespace(mesh=SimpleNamespace(num_workers=1584),
                           points=points,
                           sweep_devices=lambda: [None] * chips)
    return harness.kernel_shapes(plan)


@pytest.mark.parametrize("reader", ["steal_compact_roofline",
                                    "deque_apply_roofline"])
def test_a_one_point_sweep_needs_the_bytes_of_simulate(reader):
    """Under vmap one kernel call serves every point on its chip: a
    one-point sweep reads as `simulate` does, eight points on one chip
    need eight times the bytes, and on four chips two per call."""
    trace = {"kernels": {reader[:-len("_roofline")]:
                         {"count": 10, "device_s": 1e-3}}}
    grid = [("global", 1)] * 8

    def read(shapes):
        run = _run([_answer(0.0, 1.0, 1, 1)], trace=trace, shapes=shapes)
        return harness.metric_reader(reader)(run)

    alone = read(_shapes(None))
    assert _shapes(grid[:1]) == _shapes(None)
    assert read(_shapes(grid[:1])) == alone
    assert read(_shapes(grid)) == pytest.approx(8 * alone)
    assert read(_shapes(grid, chips=4)) == pytest.approx(2 * alone)
    # deque_apply: 1584 workers x 184 B x 10 calls in 1 ms over 819 GB/s
    if reader == "deque_apply_roofline":
        assert alone == pytest.approx(100 * 1584 * 184 * 10 / 819e9 / 1e-3)


def _answer(t0, t1, ticks, events):
    return harness.Answer(seed=0, t0=t0, t1=t1, result=None, ticks=ticks,
                          events=events)


def _run(answers, **kw):
    window = answers[-1].t1 - answers[0].t0
    base = dict(answers=answers, window_s=window, trace=None,
                peak_bytes=0, setup_s=1.0, compile_s=0.5, tables_s=None,
                shapes={}, device_kind="TPU v5 lite")
    base.update(kw)
    return SimpleNamespace(**base)


def test_rate_over_whole_answers_with_an_overrun():
    # A 10 s window: three answers of 4 s, the last overruns to 12 s.
    run = _run([_answer(0.0, 4.0, 1000, 400),
                _answer(4.0, 8.0, 1100, 420),
                _answer(8.0, 12.0, 900, 380)])
    rate = harness.metric_reader("sim_ticks_per_s")(run)
    assert rate == pytest.approx(3000 / 12.0)
    assert harness.metric_reader("stepper.ticks_per_event")(run) == \
        pytest.approx(3000 / 1200)
    assert harness.metric_reader("stepper.ms_per_event")(run) == \
        pytest.approx(12000.0 / 1200)


def test_setup_and_memory_readers():
    run = _run([_answer(0.0, 1.0, 1, 1)], peak_bytes=3 * 2**20,
               tables_s=2.5)
    assert harness.metric_reader("setup_s")(run) == 1.0
    assert harness.metric_reader("setup.compile_s")(run) == 0.5
    assert harness.metric_reader("setup.tables_s")(run) == 2.5
    assert harness.metric_reader("peak_hbm_mib")(run) == 3.0
    run.tables_s = None
    assert harness.metric_reader("setup.tables_s")(run) is None


def test_answer_seeds_advance_and_wrap():
    assert cells.answer_seed(5, 0) == 5
    assert cells.answer_seed(5, 2) == 7
    big = 2**31 + 17
    assert cells.answer_seed(big, 0) == 17
    assert all(0 <= cells.answer_seed(2**33 - 1, i) < 2**31
               for i in range(4))


def test_a_seed_pool_gives_every_run_the_same_answers():
    pool = [cells.answer_seed(2**31 + 5, i, pool=4) for i in range(4)]
    assert pool == [1, 2, 3, 0]        # (2**31 + 5) mod 4 = 1
    other = [cells.answer_seed(7, i, pool=4) for i in range(4)]
    assert sorted(pool) == sorted(other) == [0, 1, 2, 3]
    assert cells.answer_seed(7, 5, pool=4) == 0


@pytest.mark.parametrize("seed", [0, 3, 7, 2**31 - 1, 2**31 + 99, 2**33 + 5])
@pytest.mark.parametrize("pool", [None, 4, 6])
def test_the_extra_answer_lies_outside_the_pool(seed, pool):
    s = cells.extra_seed(seed, pool)
    assert (pool or 0) <= s < 2**31
    assert s == cells.extra_seed(seed, pool)
    assert s not in {cells.answer_seed(seed, i, pool) for i in range(6)} \
        or not pool
    assert cells.extra_seed(seed + 1, pool) != s


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
