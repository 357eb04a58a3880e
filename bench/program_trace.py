"""The program's own layer names, read from the profiler's traces.

Device scopes. The simulator wraps each layer of its device loop in a
`jax.named_scope` (`SCOPES`). A scope name lands in the `op_name` metadata
of every op it encloses. A TPU op event carries no such stat, so each op
name's path comes from the loop's compiled HLO text
(`simulator.lower_simulate`, or `simulator.lower_simulate_sweep` for a
sweep, compiled again from the compile cache, so with the op names the
device ran), read once per distinct op name. The plain
form that a test can hold as JSON is

    {"devices": {plane: [[op, dur_ns], ...]}, "paths": {op: path}}

and `device_scopes` gives each scope's device seconds, averaged over the
chips: an op belongs to the innermost scope of `SCOPES` found in its path
(an op the compiler made without metadata: its fused root's or its
caller's, `hlo_op_paths`), and an op in none of them (or of another
program, unless its name is also the loop's) to `UNSCOPED`.
The window is the profiler's first 3 s: the start of the window's first
answer, not the whole of it.

Host spans and step counters. `simulate` writes its host spans
(`simulate` over `simulate.prepare`, `.dispatch`, `.wait`, `.fetch`,
`.finalize`) on the profiler's clock, and the root span's arguments carry
the loop's exact step counters (`COUNTERS`). `simulate_sweep` writes the
same children under a root span of its own name, whose counters are
summed over the grid's points. The window's answers outlast
the device window, so `profile_answer` makes the window's first answer once
more under a profiler of its own, host only (`tpu_trace_mode`
`TRACE_ONLY_HOST`), in `bench/out/trace_host`, and `host_summary` reduces
its spans.

A program that names no scope or writes no span reads as `None`, and so
does every metric drawn from it: the readers then report nothing. A
program that names scopes, on a trace with TPU ops none of which maps to
one, is an error.
"""

from __future__ import annotations

import re
import shutil
import sys
import time
from pathlib import Path

from bench import trace_reduce

SCOPES = ("stepper.tick", "tick.linkstate", "tick.preshed", "tick.arrivals",
          "tick.grant", "tick.commit", "tick.recorder", "stepper.leap",
          "stepper.famine")
UNSCOPED = "unscoped"
ROOT_SPANS = ("simulate", "simulate_sweep")
CHILD_SPANS = ("simulate.prepare", "simulate.dispatch", "simulate.wait",
               "simulate.fetch", "simulate.finalize")
COUNTERS = ("ticks", "iterations", "leap_ticks", "famine_ticks",
            "famine_replays")
HOST_MODE = {"tpu_trace_mode": "TRACE_ONLY_HOST"}


def scope_of(path: str) -> str | None:
    """The innermost scope of `SCOPES` named in an `op_name` path."""
    return next((part for part in reversed(path.split("/"))
                 if part in SCOPES), None)


def newest_xplane(trace_dir: Path) -> Path | None:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def _profile_data(path: Path):
    import jax
    return jax.profiler.ProfileData.from_file(str(path))


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([^\s(]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([^\s=]+) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLED = re.compile(r"\b(calls|body|condition|to_apply|true_computation|"
                     r"false_computation|branch_computations|"
                     r"called_computations)=(\{[^}]*\}|%[^\s,]+)")


def hlo_op_paths(text: str) -> dict:
    """`{instruction: op_name path}` of a compiled program's HLO text.
    The compiler makes ops with no metadata of its own (fusions, copies,
    reductions it splits or moves). A fusion without one takes the path of
    its fused computation's root, fusions nested, else of that
    computation's last named op; any other op takes the path of the op
    that calls its computation, so that a copy inside a `cond` branch
    takes the `cond`'s scope."""
    own, comp_of, fused, caller, root, last = {}, {}, {}, {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        comp_of[name] = comp
        if line.lstrip().startswith("ROOT "):
            root[comp] = name
        path = _OP_NAME.search(line)
        if path:
            own[name] = last[comp] = path.group(1)
        for kind, callees in _CALLED.findall(line):
            for callee in re.findall(r"%([^\s,}]+)", callees):
                caller.setdefault(callee, name)
                if kind == "calls":
                    fused[name] = callee

    def inside(name: str) -> str:
        if name in own or name not in fused:
            return own.get(name, "")
        callee = fused[name]
        return inside(root.get(callee, "")) or last.get(callee, "")

    def path_of(name: str) -> str:
        up = caller.get(comp_of.get(name))
        return inside(name) or (path_of(up) if up is not None else "")

    return {name: path_of(name) for name in comp_of}


def program_op_paths(plan) -> dict | None:
    """`{op: op_name}` of the loop that `plan`'s answers run, compiled once
    more: the compile cache holds it, so its op names are the ones the
    device ran. A sweep's loop is lowered by
    `simulator.lower_simulate_sweep`, called as `simulate_sweep` is. None
    for a program without the lowering function, which names no scopes."""
    if plan.points is not None:
        lower = getattr(plan.m.simulator, "lower_simulate_sweep", None)
        if lower is None:
            return None
        lowered = lower(plan.job, plan.mesh, plan.cfg,
                        plan.point_configs(0),
                        devices=plan.sweep_devices(), **plan.kwargs)
    else:
        lower = getattr(plan.m.simulator, "lower_simulate", None)
        if lower is None:
            return None
        lowered = lower(plan.job, plan.mesh, plan.cfg, **plan.kwargs)
    return hlo_op_paths(lowered.compile().as_text())


def device_ops(path: Path) -> dict:
    """`{plane: [[op, dur_ns], ...]}`: the op line of each TPU plane of one
    trace, control-flow ops left out, each op name once with its summed
    device time."""
    devices: dict = {}
    for plane in _profile_data(path).planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        total: dict = {}
        for line in plane.lines:
            if line.name not in trace_reduce.OP_LINES:
                continue
            for ev in line.events:
                name = trace_reduce.op_name(ev.name)
                total[name] = total.get(name, 0) + int(ev.duration_ns)
        devices[plane.name] = [
            [name, ns] for name, ns in total.items()
            if trace_reduce.op_base(name) not in trace_reduce.CONTROL_OPS]
    return devices


def with_paths(devices: dict, op_paths: dict) -> dict:
    """The plain form of `device_scopes`: the ops, and the path of each
    distinct op name, looked up once."""
    names = {name for ops in devices.values() for name, _ in ops}
    return {"devices": devices,
            "paths": {name: op_paths.get(name, "") for name in names}}


def device_scopes(norm: dict) -> dict | None:
    """Device seconds per scope, and `UNSCOPED`, averaged over the chips;
    None when no op names a scope."""
    scope = {name: scope_of(p) or UNSCOPED
             for name, p in norm["paths"].items()}
    out: dict = {}
    for ops in norm["devices"].values():
        for name, dur in ops:
            key = scope.get(name, UNSCOPED)
            out[key] = out.get(key, 0.0) + dur / 1e9
    if not any(k != UNSCOPED for k in out):
        return None
    n_dev = max(len(norm["devices"]), 1)
    return {k: v / n_dev for k, v in out.items()}


def load_spans(path: Path) -> tuple[list, int]:
    """The program's host spans, `[name, start_ns, dur_ns, args]`, in
    start order, and the number of device events beside them."""
    spans, device_events = [], 0
    for plane in _profile_data(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            device_events += sum(len(line.events) for line in plane.lines)
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ROOT_SPANS or ev.name in CHILD_SPANS:
                    spans.append([ev.name, int(ev.start_ns),
                                  int(ev.duration_ns), dict(ev.stats)])
    return sorted(spans, key=lambda s: s[1]), device_events


def host_summary(spans: list) -> dict | None:
    """The last `simulate` (or `simulate_sweep`) span: its seconds, its
    children's seconds, and its counters; None when the trace holds none
    that carries them. `simulate_s` keeps its name for either root."""
    roots = [s for s in spans if s[0] in ROOT_SPANS
             and all(k in s[3] for k in COUNTERS)]
    if not roots:
        return None
    _, s0, d0, args = roots[-1]
    children: dict = {}
    for name, s, d, _ in spans:
        if name in CHILD_SPANS and s0 <= s and s + d <= s0 + d0:
            children[name] = children.get(name, 0.0) + d / 1e9
    return {"simulate_s": d0 / 1e9, "children_s": children,
            "counters": {k: int(args[k]) for k in COUNTERS}}


def counter_shares(summary: dict | None, famine_batch: int) -> dict:
    """The host metrics of one `host_summary`, in %: each skipped kind of
    tick over all ticks, the replayed ticks over the replay steps run, and
    the answer's host time outside `simulate.wait`. A metric whose base is
    zero is left out."""
    if summary is None:
        return {}
    c = summary["counters"]
    out = {}
    if c["ticks"] > 0:
        out["leap.tick_share"] = 100.0 * c["leap_ticks"] / c["ticks"]
        out["famine.tick_share"] = 100.0 * c["famine_ticks"] / c["ticks"]
    if c["famine_replays"] > 0 and famine_batch > 0:
        out["famine.useful_share"] = (100.0 * c["famine_ticks"]
                                      / (famine_batch * c["famine_replays"]))
    wait = summary["children_s"].get("simulate.wait")
    if wait is not None and summary["simulate_s"] > 0:
        out["simulate.host_share"] = (100.0 * (summary["simulate_s"] - wait)
                                      / summary["simulate_s"])
    return out


def profile_answer(run, out_dir: Path) -> dict:
    """Make the window's first answer again under a host-only profile in
    `out_dir`, and return its host metrics (`counter_shares`). Prints the
    profile's size and what it holds on standard error."""
    import jax
    plan = _plan(run)
    seed = run.answers[0].seed
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.advanced_configuration = HOST_MODE
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        plan.run(seed)
    finally:
        jax.profiler.stop_trace()
    took = time.perf_counter() - t0
    path = newest_xplane(out_dir)
    if path is None:
        return {}
    spans, device_events = load_spans(path)
    summary = host_summary(spans)
    print(f"bench: host profile of answer seed {seed}: {took:.3f} s, "
          f"{path.stat().st_size} bytes, {len(spans)} program spans, "
          f"{device_events} device events, counters "
          f"{summary and summary['counters']}", file=sys.stderr)
    return counter_shares(summary, int(getattr(plan.cfg, "famine_batch", 0)))


def _once(run, key: str, compute):
    """`compute()` once per run: the readers of one run share the
    reduction of each trace, kept on the run's own context."""
    cache = vars(run).setdefault("program_trace", {})
    if key not in cache:
        cache[key] = compute()
    return cache[key]


def _plan(run):
    """The run's answer, built again: the harness has let its own go."""
    from bench import cells
    return _once(run, "plan",
                 lambda: cells.build(run.cell, cells.program_modules(),
                                     devices=getattr(run, "devices", None)))


def _device_trace_scopes(run) -> dict | None:
    """`device_scopes` of the `--trace 1` window. None where the trace
    holds no TPU op or the program names no scopes; an error where it
    does name them and no op of the trace maps to one, so that a scope
    reader that reads nothing on the chip fails the run."""
    from bench import harness
    path = newest_xplane(harness.OUT_DIR / "trace")
    t0 = time.perf_counter()
    devices = device_ops(path) if path is not None else {}
    if not any(devices.values()):
        return None
    t1 = time.perf_counter()
    op_paths = program_op_paths(_plan(run))
    if op_paths is None:
        return None
    norm = with_paths(devices, op_paths)
    paths = norm["paths"].values()
    print(f"bench: device window: {len(paths)} op names read in "
          f"{t1 - t0:.3f} s, {sum(scope_of(p) is not None for p in paths)} "
          f"in a scope of the compiled program, its paths in "
          f"{time.perf_counter() - t1:.3f} s", file=sys.stderr)
    scopes = device_scopes(norm)
    if scopes is None:
        raise RuntimeError(
            f"bench: no device op of {path} maps to a scope of the compiled "
            f"program ({len(op_paths)} ops with an op_name)")
    return scopes


def scope_shares(scopes: dict | None) -> dict:
    """Each scope's per cent of the busy device time of `device_scopes`
    (a scope's own ops, outside its child scopes), and `UNSCOPED`'s; they
    sum to 100. Empty when no op names a scope."""
    total = sum((scopes or {}).values())
    if total <= 0:
        return {}
    return {k: 100.0 * scopes.get(k, 0.0) / total
            for k in SCOPES + (UNSCOPED,)}


def scope_share(run, scope: str) -> float | None:
    """`scope`'s share of the run's `--trace 1` window (`scope_shares`)."""
    if not run.trace:
        return None
    return scope_shares(_once(run, "scopes",
                              lambda: _device_trace_scopes(run))).get(scope)


def host_metric(run, name: str) -> float | None:
    """A metric of the host-only profile of the run's first answer."""
    if not run.trace or not run.answers:
        return None
    from bench import harness
    return _once(run, "host", lambda: profile_answer(
        run, harness.OUT_DIR / "trace_host")).get(name)
