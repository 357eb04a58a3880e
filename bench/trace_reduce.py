"""From a profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` that `jax.profiler` wrote and keeps what the
reduction needs, in a plain form that a test can hold as JSON (ops by their
own names, `op_name`; control-flow ops left out):

    {"devices": {plane: [[op, start_ns, dur_ns], ...]},
     "host": [[span, start_ns, dur_ns], ...],
     "window_ns": [start, end]}

`devices` holds the ops of each TPU plane's op line; `host` holds the
benchmark's own `bench.*` spans; the window runs from the first op's start
to the last op's end. `reduce` then gives the device's busy time (the
union of op intervals, averaged over the chips), each kernel's event count
and device time (ops found by the kernel's Pallas name), and
the `breakdown`: the ops that took most device time, and the longest idle
gaps, each named by the benchmark span that the host was in.
"""

from __future__ import annotations

import json
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
HOST_SPAN_PREFIX = "bench."
# Control flow encloses the ops of its body on the same line; only the
# leaves count as device work.
CONTROL_OPS = ("while", "conditional", "call")
TOP = 10


def op_name(event_name: str) -> str:
    """An op's own name: the trace names an op by its HLO text,
    `%steal_compact.7 = (s32[...]) custom-call(...)`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_base(name: str) -> str:
    """An op's name without its numeric suffix, so a Pallas kernel is found
    by the name its `pallas_call` gave it: `steal_compact.7` is
    `steal_compact`."""
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def load(trace_dir: Path) -> dict:
    """The plain form of the newest trace under `trace_dir`."""
    import jax
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    for ev in line.events:
                        name = op_name(ev.name)
                        if op_base(name) not in CONTROL_OPS:
                            ops.append([name, int(ev.start_ns),
                                        int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith(HOST_SPAN_PREFIX))
    starts = [s for ops in devices.values() for _, s, _ in ops]
    ends = [s + d for ops in devices.values() for _, s, d in ops]
    window = [min(starts), max(ends)] if starts else [0, 0]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1]),
            "window_ns": window}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_span_at(host, t_ns, default: str) -> str:
    """The innermost benchmark span that covers `t_ns`, else `default`."""
    best = None
    for name, s, d in host:
        if s <= t_ns <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else default


def reduce(norm: dict, kernels=(), default_span: str = "host") -> dict:
    """Busy and window seconds, per-kernel counts and device seconds, and
    the breakdown, from the plain form. A span is recorded only once it
    ends, so a gap inside a span that outlived the profiler is named
    `default_span`: what the caller knows the host was doing then."""
    w0, w1 = norm["window_ns"]
    window_ns = max(w1 - w0, 0)
    busy, per_op, kern, gaps = [], {}, {k: {"count": 0, "device_s": 0.0}
                                         for k in kernels}, []
    for plane, ops in sorted(norm["devices"].items()):
        iv = _union([(max(s, w0), min(s + d, w1)) for _, s, d in ops
                     if s + d > w0 and s < w1])
        busy.append(sum(e - s for s, e in iv))
        for a, b in zip(iv, iv[1:]):
            gaps.append((b[0] - a[1], a[1], b[0]))
        for name, s, d in ops:
            per_op[name] = per_op.get(name, 0) + d
            k = kern.get(op_base(name))
            if k is not None:
                k["count"] += 1
                k["device_s"] += d / 1e9
    n_dev = max(len(norm["devices"]), 1)
    gaps.sort(reverse=True)
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / n_dev / 1e9,
        "window_s": window_ns / 1e9,
        "kernels": kern,
        "breakdown": {
            "device_ops": [[n, d / n_dev / 1e9] for n, d in top_ops],
            "idle_gaps": [[_host_span_at(norm["host"], (s + e) // 2,
                                         default_span),
                           g / 1e9] for g, s, e in gaps[:TOP]],
        },
    }


def write_summary(norm: dict, path: Path, max_ops: int = 400):
    """A small plain-form excerpt of the trace, for reading by hand: the
    first `max_ops` ops of each plane, every host span, and the window
    that those ops span."""
    devices = {p: ops[:max_ops] for p, ops in norm["devices"].items()}
    starts = [s for ops in devices.values() for _, s, _ in ops]
    ends = [s + d for ops in devices.values() for _, s, d in ops]
    small = {"devices": devices, "host": norm["host"],
             "window_ns": [min(starts), max(ends)] if starts else [0, 0]}
    Path(path).parent.mkdir(parents=True, exist_ok=True)
    Path(path).write_text(json.dumps(small))
