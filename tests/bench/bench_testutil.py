"""Shared helpers of the benchmark's tests: the repository root on the
import path, and tiny deployments that run on the CPU in seconds."""

from __future__ import annotations

import copy
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# A few workers and short jobs: the shapes change, every other key of the
# real files stays.
TINY_CONFIGS = {
    "starlink-s1": {"mesh": {"kind": "grid", "rows": 6, "cols": 4,
                             "torus": True},
                    "dynamics": {"orbit_ticks": 64, "warn_ticks": 8}},
}
TINY_JOBS = {
    "fib": {"n": 14, "cutoff": 8},
}


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def tiny_config(name: str) -> dict:
    conf = load_json(ROOT / "bench" / "configs" / f"{name}.json")
    small = TINY_CONFIGS[name]
    conf["mesh"] = small["mesh"]
    if "dynamics" in conf:
        conf["dynamics"] = {**conf["dynamics"], **small["dynamics"]}
    return conf


def tiny_traffic(name: str) -> dict:
    tr = copy.deepcopy(load_json(ROOT / "bench" / "traffic" / f"{name}.json"))
    tr["job"].update(TINY_JOBS[tr["job"]["type"]])
    if "trace" in tr:
        tr["trace"]["ring_capacity"] = 1 << 14
    return tr


def tiny_root(tmp_path: Path) -> Path:
    """A copy of the benchmark in `tmp_path` whose configurations and
    traffic mixes are cut to a CPU's size, with the same cells."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = load_json(ROOT / "BENCHMARK.json")
    for c in bench["configs"]:
        (root / c["file"]).write_text(json.dumps(tiny_config(c["name"])))
    for w in bench["workloads"]:
        path = root / "bench" / "traffic" / f"{w['traffic']}.json"
        path.write_text(json.dumps(tiny_traffic(w["traffic"])))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
