"""The benchmark's one generator: a cell's answers, built from data.

A cell (`workloads` entry of `BENCHMARK.json`) names a deployment
(`configs[].file`, a JSON file of sizes) and a traffic mix
(`bench/traffic/<traffic>.json`, a JSON file of parameters). This module
reads both and builds the same answer twice over: once through the program
under test (`repro.core`, platform defaults) and once through the frozen
reference (`bench.reference`, one tick per loop iteration), so that neither
takes anything the other made. Inputs that the deployment fixes, such as the eclipse and seam
schedule, are generated here from the configuration's own seed; what
varies between answers is the answer's seed alone.

Configuration key `mesh` (the others are sizes named in the file):

  mesh            {"kind": "grid", "rows", "cols", "torus"}:
                  `MeshTopology.grid`; or {"kind": "square", "workers",
                  "torus"}: `MeshTopology.square`, ceil(sqrt(workers)) wide,
                  rows filled in order, the last one ragged

Traffic keys (all but `answer` and `job` optional):

  answer          "simulate": one `simulator.simulate` call per answer;
                  "sweep": one `simulator.simulate_sweep` call per answer
                  over the points of `grid`, on the cell's own chips; the
                  reference makes the same points one `simulate` call
                  each. Every point of one answer takes that answer's seed.
  grid            (sweep only) {"strategy": [names], "hop_ticks": [ticks]}:
                  the points are their Cartesian product, strategy-major
                  (every τ of the first strategy, then of the next)
  job             {"type": "fib", "n", "cutoff", "max_leaf_cost"} or
                  {"type": "uts", "b0", "d_max", "root_seed"}
  strategy        (simulate only) victim-selection strategy, by name
  seed_pool       the window cycles through this many answers, starting at
                  the run's seed (default: each answer takes a new seed)
  orbits          one answer runs this many orbits of the deployment's
                  eclipse and seam schedule (needs `dynamics` in the
                  deployment); sets the tick cap. Without it the job
                  drains.
  routing         layout of the link-state routing tables: "auto" (the
                  program's choice by W; default), "dense" or "sparse"
  preshed         predictable shutdowns shed their deque first
  arrivals        open-loop requests: {"batch", "mean_gap_ticks",
                  "load_per_worker_tick", "num_stations", "zipf_s",
                  "station_seed"}; the request cost is solved for the load
  trace           flight recorder: {"ring_capacity", "bins"}
"""

from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
SEED_MOD = 1 << 31          # SimParams.seed is int32
DRAIN_MAX_TICKS = 1 << 24   # "drains": far above any answer's length


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def config_path(bench: dict, name: str, root: Path = ROOT) -> Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return Path(root) / c["file"]
    raise KeyError(f"BENCHMARK.json has no configuration {name!r}")


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "traffic" / f"{name}.json"


def load_cell(name: str, root: Path = ROOT, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    w = entries[0]
    config = json.loads(config_path(bench, w["config"], root).read_text())
    traffic = json.loads(traffic_path(w["traffic"], root).read_text())
    return Cell(name=name, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic)


def program_modules() -> SimpleNamespace:
    """The system under test."""
    from repro.core import (arrivals, linkstate, simulator, stealing, tasks,
                            topology, tracing)
    return SimpleNamespace(arrivals=arrivals, linkstate=linkstate,
                           simulator=simulator, stealing=stealing,
                           tasks=tasks, topology=topology, tracing=tracing)


def reference_modules() -> SimpleNamespace:
    """The frozen reference; imports nothing of the program."""
    from bench.reference import (arrivals, linkstate, simulator, stealing,
                                 tasks, topology, tracing)
    return SimpleNamespace(arrivals=arrivals, linkstate=linkstate,
                           simulator=simulator, stealing=stealing,
                           tasks=tasks, topology=topology, tracing=tracing)


# --------------------------------------------------------------------------
# Inputs generated from data
# --------------------------------------------------------------------------

def make_mesh(m, spec: dict):
    if spec["kind"] == "grid":
        return m.topology.MeshTopology.grid(int(spec["rows"]),
                                            int(spec["cols"]),
                                            torus=bool(spec["torus"]))
    if spec["kind"] == "square":
        return m.topology.MeshTopology.square(int(spec["workers"]),
                                              torus=bool(spec["torus"]))
    raise ValueError(f"unknown mesh kind {spec['kind']!r}")


def make_job(m, job: dict):
    if job["type"] == "fib":
        return m.tasks.FibWorkload(n=int(job["n"]), cutoff=int(job["cutoff"]),
                                   max_leaf_cost=int(job["max_leaf_cost"]))
    if job["type"] == "uts":
        return m.tasks.UtsWorkload(b0=float(job["b0"]),
                                   d_max=int(job["d_max"]),
                                   root_seed=int(job["root_seed"]))
    raise ValueError(f"unknown job type {job['type']!r}")


def horizon_ticks(cell: Cell) -> int:
    tr = cell.traffic
    if "orbits" in tr:
        return int(tr["orbits"]) * int(cell.config["dynamics"]["orbit_ticks"])
    return DRAIN_MAX_TICKS


def drains(cell: Cell) -> bool:
    """Every answer of the cell runs its job to the end (no orbit cap, no
    arrivals), so its result and node count have a closed form."""
    tr = cell.traffic
    return "orbits" not in tr and not tr.get("arrivals")


def eclipse_schedule(cell: Cell):
    """The deployment's eclipse, wake and seam schedule over one answer,
    from its configuration alone (the reference's orbit model)."""
    from bench.reference import constellation
    d = cell.config["dynamics"]
    mesh = cell.config["mesh"]
    ccfg = constellation.ConstellationConfig(
        planes=int(mesh["rows"]), sats_per_plane=int(mesh["cols"]),
        orbit_ticks=int(d["orbit_ticks"]), tau_base=int(cell.config["hop_ticks"]),
        interplane_amp=float(d["interplane_amp"]),
        eclipse_fraction=float(d["eclipse_fraction"]),
        battery_limited_frac=float(d["battery_limited_frac"]),
        warn_ticks=int(d["warn_ticks"]), wraparound=bool(mesh["torus"]),
        seed=int(d["seed"]), epochs_per_orbit=int(d["epochs_per_orbit"]),
        seam_outage_frac=float(d["seam_outage_frac"]))
    return constellation.Constellation(ccfg).schedule(
        horizon_ticks=horizon_ticks(cell))


def request_cost(cell: Cell, workers: int) -> int:
    a = cell.traffic["arrivals"]
    return max(1, round(float(a["load_per_worker_tick"]) * workers
                        * float(a["mean_gap_ticks"]) / int(a["batch"])))


def answer_seed(seed: int, index: int, pool: int | None = None) -> int:
    """Seed of answer `index` of a window that starts at `seed`: each
    answer takes the next seed. With a `pool` of P answers, the window
    cycles through the same P answers (seeds 0 .. P − 1), starting at
    answer `seed` mod P: every run does the same work in another order,
    since the victim draws, and with them the ticks per event, change from
    seed to seed."""
    if pool:
        return (int(seed) + index) % int(pool)
    return (int(seed) + index) % SEED_MOD


def extra_seed(seed: int, pool: int | None = None) -> int:
    """Seed of the run's extra answer, made after the window and left out
    of its metrics: drawn from `seed` and outside the pool, so every run
    checks one answer that no other seed's run need share."""
    p = int(pool or 0)
    return p + int(seed) % (SEED_MOD - p)


def grid_points(m, grid: dict) -> list[tuple]:
    """A sweep's points, `(Strategy, hop_ticks)`, strategy-major."""
    return [(m.stealing.Strategy(s), int(t))
            for s in grid["strategy"] for t in grid["hop_ticks"]]


@dataclasses.dataclass
class Plan:
    """One cell's answer, ready to run on one side (program or reference)."""
    m: SimpleNamespace
    cell: Cell
    mesh: object
    job: object
    cfg: object              # SimConfig of one answer (seed set per call)
    kwargs: dict             # schedules and prebuilt tables
    timings: dict            # host seconds of the table builds
    points: list | None = None    # a sweep's (strategy, hop_ticks) points
    devices: list | None = None   # the chips a sweep runs on

    def seed(self, seed: int, index: int) -> int:
        """The seed of the window's answer `index` (see `answer_seed`)."""
        return answer_seed(seed, index, self.cell.traffic.get("seed_pool"))

    def point_configs(self, seed: int) -> list:
        """A sweep answer's points as SimConfigs, each with the answer's
        seed."""
        return [dataclasses.replace(self.cfg, strategy=s, hop_ticks=t,
                                    seed=int(seed))
                for s, t in self.points]

    def sweep_devices(self) -> list:
        """The chips a sweep runs on: the harness's, else the first
        `chips` local devices, never every device of the host."""
        if self.devices is not None:
            return list(self.devices)
        import jax
        return jax.local_devices()[:self.cell.chips]

    def run(self, seed: int, kwargs: dict | None = None):
        """One answer: the program's (or reference's) `simulate`, with the
        results fetched; for a sweep, `simulate_sweep` and its list of
        results, one per point."""
        kw = self.kwargs if kwargs is None else kwargs
        if self.points is not None:
            return self.m.simulator.simulate_sweep(
                self.job, self.mesh, self.cfg, self.point_configs(seed),
                devices=self.sweep_devices(), **kw)
        cfg = dataclasses.replace(self.cfg, seed=int(seed))
        return self.m.simulator.simulate(self.job, self.mesh, cfg, **kw)

    def dead_kwargs(self) -> dict:
        """The answer's arguments with every worker dead from tick 0: the
        same shapes, so the same compiled programs, for a warm-up that
        runs almost nothing."""
        W = self.mesh.num_workers
        kw = dict(self.kwargs)
        kw["fail_time"] = np.zeros(W, np.int32)
        kw["wake_time"] = -np.ones(W, np.int32)
        kw["fail_period"] = -np.ones(W, np.int32)
        return kw


def build(cell: Cell, m: SimpleNamespace, devices=None) -> Plan:
    """Build one side's answer of `cell` with the modules `m`, each at its
    own defaults: the program's are the platform's backends and stepper.
    A sweep runs on `devices` (see `Plan.sweep_devices`)."""
    conf, tr = cell.config, cell.traffic
    if tr["answer"] == "simulate":
        points = None
        strategy, hop = m.stealing.Strategy(tr["strategy"]), conf["hop_ticks"]
    elif tr["answer"] == "sweep":
        points = grid_points(m, tr["grid"])
        strategy, hop = points[0]
    else:
        raise ValueError(f"unknown answer kind {tr['answer']!r}")
    mesh = make_mesh(m, conf["mesh"])
    W = mesh.num_workers
    job = make_job(m, tr["job"])
    timings: dict = {}
    kwargs: dict = {}
    cfg_kw = dict(strategy=strategy, hop_ticks=int(hop),
                  capacity=int(conf["capacity"]),
                  max_ticks=horizon_ticks(cell))
    if "orbits" in tr:
        sched = eclipse_schedule(cell)
        ls = m.linkstate.LinkStateSchedule(**{
            f.name: getattr(sched.linkstate, f.name)
            for f in dataclasses.fields(sched.linkstate)})
        t0 = time.perf_counter()
        tbl, _ = m.linkstate.build_tables(
            ls, mesh, routing=m.linkstate.resolve_routing(
                tr.get("routing", "auto"), W))
        timings["linkstate"] = time.perf_counter() - t0
        kwargs.update(
            fail_time=np.where(sched.predictable, sched.fail_time,
                               -1).astype(np.int32),
            wake_time=sched.wake_time, fail_period=sched.fail_period,
            linkstate=tbl)
        if tr.get("preshed"):
            cfg_kw.update(preshed=True,
                          warn_ticks=int(conf["dynamics"]["warn_ticks"]))
    else:
        kwargs.update(fail_time=-np.ones(W, np.int32))
    a = tr.get("arrivals")
    if a:
        acfg = m.arrivals.ArrivalConfig(
            task_cost=request_cost(cell, W),
            num_stations=int(a.get("num_stations", 0)),
            zipf_s=float(a.get("zipf_s", 0.0)),
            station_seed=int(a.get("station_seed", 0)))
        t0 = time.perf_counter()
        kwargs["arrivals"] = m.arrivals.device_tables(acfg, mesh)
        timings["arrivals"] = time.perf_counter() - t0
        cfg_kw.update(arrival_batch=int(a["batch"]),
                      arrival_gap_q8=int(round(256 * float(
                          a["mean_gap_ticks"]))))
    t = tr.get("trace")
    if t:
        h = horizon_ticks(cell)
        bins = int(t["bins"])
        cfg_kw["trace"] = m.tracing.TraceConfig(
            ring_capacity=int(t["ring_capacity"]), bins=bins,
            bin_ticks=-(-h // bins))
    cfg = m.simulator.SimConfig(**cfg_kw)
    return Plan(m=m, cell=cell, mesh=mesh, job=job, cfg=cfg, kwargs=kwargs,
                timings=timings, points=points,
                devices=None if devices is None else list(devices))


def expected(cell: Cell) -> dict | list | None:
    """Closed form of every drained answer, from the reference's task
    tree: result checksum and node count; for a sweep, a list with one
    per point (every point drains the same tree)."""
    if not drains(cell):
        return None
    rm = reference_modules()
    job = make_job(rm, cell.traffic["job"])
    closed = {"result": int(job.expected_result()),
              "nodes": int(job.expected_nodes())}
    if cell.traffic["answer"] == "sweep":
        return [closed] * len(grid_points(rm, cell.traffic["grid"]))
    return closed
