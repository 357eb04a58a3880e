"""One run of one cell: set-up, the measured window, the traced window,
the comparison with the reference, and the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (`setup_s`) holds everything before the first answer starts: the
interpreter and TPU start, building the deployment's tables, and one warm
call of each program the window drives, which loads it from the persistent
compilation cache (or compiles it, on a checkout's first run). The warm call
runs the answer's own programs with every worker dead from tick 0, so it
compiles the window's shapes and nothing else, and simulates almost nothing.

The window then runs whole answers back to back, each with the next seed
of the mix's pool, until `--seconds` have passed; the last answer may
overrun. With `--trace 1` the profiler records the first `PROFILE_S`
seconds of the window. After the window the run reads the device's peak
memory and makes one extra answer through the same call, with a seed drawn
from `--seed` outside the pool; it is left out of the metrics. It then
checks every answer against the checks that need no simulator (a drained
job's closed form; the ledger of every open-loop request), and the extra
answer and one window answer, drawn from the seed, against the reference,
which steps the same model one tick at a time. It prints its metrics: the
cell's end-to-end metrics with `--trace 0`, its per-layer metrics with
`--trace 1`.

A sweep's answer is one `simulate_sweep` call over the mix's grid, on the
cell's own chips: its ticks are the sum over its points, its loop
iterations the largest point's, its checks run on every point, and the
reference makes each point with its own `simulate` call.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import random
import shutil
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

from bench import cells, compare, trace_reduce

ROOT = cells.ROOT
OUT_DIR = ROOT / "bench" / "out"
PROFILE_S = 3.0
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(SystemExit):
    pass


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the program with JAX's non-partitionable "
                         "threefry draws, which breaks the deployment's "
                         "seed-reproducibility guarantee; `correct` must "
                         "come out false")
    return ap.parse_args(argv)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless `JAX_COMPILATION_CACHE_DIR` places it."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return env or str(CACHE_DIR)


def check_devices(chips: int):
    """The chips the cell asks for, or NoChip."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU; JAX's first device is "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs


@dataclasses.dataclass
class Answer:
    """One answer: its seed, its host-clock span, its SimResult and that
    result's ticks and loop iterations. A sweep's answer keeps its list of
    SimResults, one per point; its ticks are the sum over the points and
    its events the largest count, which is the iterations of the loop that
    the points step together."""
    seed: int
    t0: float
    t1: float
    result: object
    ticks: int
    events: int


def answer_counts(result) -> tuple[int, int]:
    """`(ticks, events)` of one answer's result (see `Answer`)."""
    if isinstance(result, list):
        return (sum(int(r.ticks) for r in result),
                max((int(r.events) for r in result), default=0))
    return int(result.ticks), int(result.events)


class Profile:
    """The profiler over the first `seconds` of the window, stopped from a
    timer so that it may close inside an answer."""

    def __init__(self, seconds: float):
        self.dir = OUT_DIR / "trace"
        self.seconds = seconds
        self.t0 = self.t1 = None
        self._lock = threading.Lock()
        self._timer = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # device ops and TraceMe spans only
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.t0 = time.perf_counter()
        self._timer = threading.Timer(self.seconds, self.stop)
        self._timer.start()

    def stop(self):
        import jax
        with self._lock:
            if self.t1 is None and self.t0 is not None:
                self.t1 = time.perf_counter()
                jax.profiler.stop_trace()

    def close(self):
        if self._timer is not None:
            self._timer.cancel()
        self.stop()


def metric_reader(name: str, root: Path = ROOT):
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell_name: str, traced: bool) -> list[dict]:
    """The metrics that the cell reports in this mode."""
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def kernel_shapes(plan) -> dict:
    """The shapes of one kernel call. `points` is how many grid points one
    call serves: 1 for `simulate`; for a sweep, which vmaps its points
    into every call, the points on each of its chips."""
    from bench.reference import deque, stealing, tasks
    points = 1
    if plan.points is not None:
        points = -(-len(plan.points) // len(plan.sweep_devices()))
    return {"workers": plan.mesh.num_workers,
            "width": deque.TASK_WIDTH, "grant_width": stealing.GRANT_WIDTH,
            "lanes": tasks.EXPAND_K + 1, "points": points}


def timed_answer(plan, seed: int, call) -> Answer:
    a0 = time.perf_counter()
    with span("bench.answer"):
        res = call(plan, seed)
    with span("bench.fetch"):
        ans = Answer(seed, a0, 0.0, res, *answer_counts(res))
    ans.t1 = time.perf_counter()
    return ans


def answer_checks(cell: cells.Cell, ans: Answer, expect, ref_plan,
                  offered: dict) -> dict:
    """The checks of one answer that need no simulator, each summed over a
    sweep's points, with `points_missing` for a sweep that returned
    another number of points than its grid has. `offered` caches the
    stream's offered requests by seed."""
    if not isinstance(ans.result, list):
        return point_checks(cell, ans.seed, ans.result, expect, ref_plan,
                            offered)
    want = len(ref_plan.points)
    expect = expect or [None] * want
    per_point = [point_checks(cell, ans.seed, r, e, ref_plan, offered)
                 for r, e in zip(ans.result, expect)]
    out: dict = {}
    for c in per_point:
        for k, v in c.items():
            out[k] = out.get(k, 0) + v
    out["points_missing"] = abs(len(ans.result) - want)
    return out


def point_checks(cell: cells.Cell, seed: int, r, expect, ref_plan,
                 offered: dict) -> dict:
    """The checks that need no simulator, on one SimResult."""
    out = {"closed_form_misses": compare.closed_form_misses(r, expect),
           "undrained": int(bool(expect) and r.ticks >= cells.DRAIN_MAX_TICKS),
           "overflow_tasks": int(r.overflow),
           "trace_dropped": compare.trace_dropped(r)}
    if cell.traffic.get("arrivals"):
        cfg = ref_plan.cfg
        key = (seed, int(r.ticks))
        if key not in offered:
            offered[key] = compare.offered_requests(
                ref_plan.kwargs["arrivals"], seed, cfg.arrival_gap_q8,
                cfg.arrival_batch, int(r.ticks))
        out.update(compare.request_ledger(
            r, offered[key], int(ref_plan.kwargs["arrivals"].task_cost)))
    return out


def run_cell(cell: cells.Cell, args, devices, *, clock0: float,
             pre_s: float = 0.0, bench: dict | None = None,
             root: Path = ROOT, entry=None) -> dict:
    """Everything of one run after the device check. Returns the result
    object; `entry(plan, seed)` replaces the answer call (for tests that
    break the timed path on purpose)."""
    import jax
    bench = bench if bench is not None else cells.load_benchmark(root)
    used = list(devices[:cell.chips])
    if args.control:
        jax.config.update("jax_threefry_partitionable", False)
    with span("bench.setup"):
        plan = cells.build(cell, cells.program_modules(), devices=used)
        call = entry or (lambda p, s: p.run(s))
        t = time.perf_counter()
        plan.run(plan.seed(args.seed, 0), plan.dead_kwargs())
        compile_s = time.perf_counter() - t

    profile = Profile(PROFILE_S) if args.trace else None
    answers: list[Answer] = []
    tw0 = time.perf_counter()
    setup_s = tw0 - clock0 + pre_s
    while True:
        if profile is not None and not answers:
            profile.start()
        answers.append(timed_answer(plan, plan.seed(args.seed, len(answers)),
                                    call))
        if answers[-1].t1 - tw0 >= args.seconds:
            break
    if profile is not None:
        profile.close()
    window_s = answers[-1].t1 - answers[0].t0
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    extra = timed_answer(plan, cells.extra_seed(
        args.seed, cell.traffic.get("seed_pool")), call)

    trace = None
    if profile is not None:
        norm = trace_reduce.load(profile.dir)
        # the profiler stops inside the window's first answer, while the
        # host waits in `bench.answer` for the device
        trace = trace_reduce.reduce(norm, kernels=("steal_compact",
                                                   "deque_apply"),
                                    default_span="bench.answer")
        trace_reduce.write_summary(norm, OUT_DIR / "trace_summary.json")

    # ---- correctness: the checks that need no simulator on every answer,
    # the reference on the extra answer and on one window answer
    shapes = kernel_shapes(plan)
    tables_s = sum(plan.timings.values()) if plan.timings else None
    del plan
    if args.control:
        jax.config.update("jax_threefry_partitionable", True)
    checked = answers + [extra]
    with span("bench.reference"):
        ref_plan = cells.build(cell, cells.reference_modules())
        expect = cells.expected(cell)
        offered: dict = {}
        per_answer = [answer_checks(cell, a, expect, ref_plan, offered)
                      for a in checked]
        pick = random.Random(args.seed).randrange(len(answers))
        t = time.perf_counter()
        for i in (pick, len(checked) - 1):
            per_answer[i]["ref_diff_values"] = compare.differing_values(
                checked[i].result, ref_plan.run(checked[i].seed))
        ref_s = time.perf_counter() - t
    print(f"bench: window {window_s:.3f} s, extra answer "
          f"{extra.t1 - extra.t0:.3f} s, after it "
          f"{time.perf_counter() - extra.t1:.3f} s, of which the reference's "
          f"two answers {ref_s:.3f} s", file=sys.stderr)
    checks = {k: sum(c.get(k, 0) for c in per_answer)
              for k in per_answer[-1]}
    failed = sum(any(c.values()) for c in per_answer)
    limits = {k: 0 for k in checks}
    correct = all(checks[k] <= limits[k] for k in checks)

    ctx = SimpleNamespace(
        cell=cell, setup_s=setup_s, compile_s=compile_s, tables_s=tables_s,
        answers=answers, window_s=window_s, peak_bytes=peak,
        trace=trace, shapes=shapes, device_kind=used[0].device_kind,
        devices=used)
    metrics = {}
    for spec in cell_metrics(bench, cell.name, bool(args.trace)):
        value = metric_reader(spec["name"], root)(ctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(checked),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = trace["breakdown"]
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in checks}
    return out


def main(argv=None, *, clock0: float | None = None, pre_s: float = 0.0) -> int:
    clock0 = time.perf_counter() if clock0 is None else clock0
    args = parse(argv)
    bench = cells.load_benchmark()
    cell = cells.load_cell(args.workload, bench=bench)
    try:
        devices = check_devices(cell.chips)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    enable_compile_cache()
    out = run_cell(cell, args, devices, clock0=clock0, pre_s=pre_s,
                   bench=bench)
    for k, v in out["checks"].items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
