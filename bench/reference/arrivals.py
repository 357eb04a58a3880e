"""Open-loop arrival traffic: compiled request generators for the simulator.

Every run used to be a *closed* system — one root task fans out and the
horizon ends when work drains — but production SEC is an *open* system:
ground stations continuously inject user requests into the constellation,
and the quantity that matters for serving real traffic is each strategy's
load–latency curve (offered load → sojourn-time percentiles), not
makespan. This module supplies the arrival side of that experiment as a
pure, compiled process the simulator can treat as a first-class event
horizon, so ``step_mode="leap"`` stays bit-identical to the tick oracle.

Candidate stream (deterministic thinning)
-----------------------------------------
Arrivals are generated from ONE global candidate stream: candidate k
fires at

    T_k = T_{k-1} + gap_k,   gap_k = max(1, round(-ln(u_k) · gap/256))

with ``gap`` the Q8.8-ish fixed-point mean inter-candidate gap
(`SimParams.arrival_gap_q8` = mean gap in ticks × 256 — a *traced* int32
leaf, so an offered-load sweep costs zero retraces) and u_k drawn from a
splittable integer hash of (seed, k) — `tasks._hash2`, the same mixer UTS
uses. Everything about candidate k (its gap, acceptance, station) is a
pure function of k and the run seed, never of how the simulator reached
tick T_k; that is what makes the next-arrival tick a carried horizon the
leap and famine windows can clip against, and what keeps tick/leap
bit-identical.

Each candidate is then *thinned* deterministically:

  * **rate schedule** — accepted only if u'_k < rate_q16[epoch(T_k)],
    a per-epoch Q16 acceptance scale riding the same `epoch_index`
    machinery `LinkStateSchedule` uses (its own `rate_starts` boundaries —
    e.g. a diurnal swing from `constellation.Constellation
    .traffic_schedule`, or a step flip mid-famine in tests);
  * **burst window** — accepted only while the on/off cycle is in its
    "on" phase (``T_k mod (on+off) < on``); ``on = off = 0`` disables the
    gate, which is the plain Poisson process.

Both gates are data (`ArrivalArrays` leaves), so Poisson and bursty
traffic share one compiled graph. An accepted candidate injects
`SimParams.arrival_batch` (≤ `ARRIVAL_K`) request records at its station;
a thinned candidate still costs one horizon visit — conservative for the
famine window (sizes provably frozen up to *every* candidate tick), never
wrong.

Ground stations (Zipf hot spots)
--------------------------------
Stations map onto mesh workers via a cumulative-weight CDF: candidate k
draws u''_k and binary-searches `station_cdf`. Weights follow a Zipf
law over shuffled station ranks (``weight ∝ 1/rank^s``; s = 0 is
uniform), so a handful of ground stations can concentrate the offered
load on a corner of the mesh — the hot-spot regime where victim-selection
strategy matters most.

Request records are ``[tasks.KIND_REQ, cost, inject_tick, task_id]``:
leaves of `tasks.expand` costing `cost` work units, with the inject tick
carried in the record so the sojourn ledger (EV_SOJOURN in
`core/tracing.py`) prices queue wait + nominal service at pop time with
no extra simulator state.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import linkstate as lstate
from . import tasks

# Max request records injected per accepted candidate (static lane width of
# the injection push; `SimParams.arrival_batch` selects 1..ARRIVAL_K).
ARRIVAL_K = 8

# Q16 acceptance scale: rate_q16 == RATE_ONE accepts every candidate.
RATE_ONE = 1 << 16

# Substream salts (arbitrary odd constants): gap / acceptance / station
# draws come from decorrelated hash streams of the same run seed.
_SALT_SEED = 0x4F50454E    # "OPEN"
_SALT_GAP = 0x41525231
_SALT_ACCEPT = 0x41525232
_SALT_STATION = 0x41525233


# --------------------------------------------------------------------------- #
# Config + device tables
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ArrivalConfig:
    """Host-side arrival-process description (the *shape* of the traffic;
    the offered load itself is the traced `SimParams.arrival_gap_q8` /
    `arrival_batch` pair, so a load sweep reuses one compilation).

    ``num_stations = 0`` makes every worker a ground station; otherwise
    `num_stations` workers are picked by `station_seed`. ``zipf_s`` skews
    station weights (0 = uniform). ``on_ticks``/``off_ticks`` gate
    candidates through a periodic burst window (both 0 = always on =
    Poisson). ``rate_starts``/``rate_scale`` is a piecewise-constant
    per-epoch acceptance schedule (fractions of the base rate in [0, 1];
    default: always 1.0)."""
    task_cost: int = 16
    num_stations: int = 0
    zipf_s: float = 0.0
    station_seed: int = 0
    on_ticks: int = 0
    off_ticks: int = 0
    rate_starts: tuple = ()
    rate_scale: tuple = ()

    def validate(self) -> "ArrivalConfig":
        if self.task_cost < 1:
            raise ValueError("arrival task_cost must be >= 1")
        if self.num_stations < 0:
            raise ValueError("num_stations must be >= 0 (0 = all workers)")
        if self.zipf_s < 0:
            raise ValueError("zipf_s must be >= 0")
        if self.on_ticks < 0 or self.off_ticks < 0:
            raise ValueError("on_ticks/off_ticks must be >= 0")
        if self.off_ticks > 0 and self.on_ticks == 0:
            raise ValueError(
                "off_ticks > 0 with on_ticks == 0 would accept nothing; "
                "set on_ticks >= 1 (or both 0 for an always-on process)")
        rs, sc = list(self.rate_starts), list(self.rate_scale)
        if len(rs) != len(sc):
            raise ValueError("rate_starts and rate_scale must have equal length")
        if rs:
            if rs[0] != 0:
                raise ValueError("rate_starts must begin at tick 0")
            if any(b <= a for a, b in zip(rs, rs[1:])):
                raise ValueError("rate_starts must be strictly increasing")
            if any(not 0.0 <= s <= 1.0 for s in sc):
                raise ValueError("rate_scale entries must lie in [0, 1]")
        return self


class ArrivalArrays(NamedTuple):
    """Device half of an `ArrivalConfig` (a traced pytree argument of
    `_sim_core`, like `LinkStateArrays` — passing None disables arrivals
    statically)."""
    station_cdf: jax.Array   # (W,) int32 inclusive cumulative station weights
    rate_starts: jax.Array   # (E,) int32 epoch boundaries of the rate schedule
    rate_q16: jax.Array      # (E,) int32 acceptance scale, RATE_ONE = 1.0
    on_ticks: jax.Array      # () int32 burst-on window length
    cycle_ticks: jax.Array   # () int32 on+off cycle length (0 = always on)
    task_cost: jax.Array     # () int32 work units per injected request


def station_weights(acfg: ArrivalConfig, num_workers: int) -> np.ndarray:
    """(W,) int64 station weights: Zipf over shuffled station ranks, zero
    for non-station workers. Deterministic in `station_seed`."""
    W = num_workers
    ns = acfg.num_stations if acfg.num_stations > 0 else W
    if ns > W:
        raise ValueError(f"num_stations {ns} exceeds num_workers {W}")
    rng = np.random.default_rng(acfg.station_seed)
    stations = (np.arange(W) if ns == W
                else np.sort(rng.choice(W, size=ns, replace=False)))
    ranks = rng.permutation(ns)  # which station is the hot one
    w = np.maximum(
        np.round(65536.0 / np.power(ranks + 1.0, acfg.zipf_s)), 1.0)
    weights = np.zeros(W, np.int64)
    weights[stations] = w.astype(np.int64)
    return weights


def device_tables(acfg: ArrivalConfig, mesh) -> ArrivalArrays:
    """Build the device pytree for a mesh. Validates host-side."""
    acfg.validate()
    weights = station_weights(acfg, mesh.num_workers)
    cdf = np.cumsum(weights)
    if cdf[-1] >= 2**31:
        raise ValueError("total station weight must stay below 2**31")
    if acfg.rate_starts:
        rs = np.asarray(acfg.rate_starts, np.int32)
        rq = np.round(np.asarray(acfg.rate_scale, np.float64)
                      * RATE_ONE).astype(np.int32)
    else:
        rs = np.zeros(1, np.int32)
        rq = np.full(1, RATE_ONE, np.int32)
    cycle = acfg.on_ticks + acfg.off_ticks
    return ArrivalArrays(
        station_cdf=jnp.asarray(cdf, jnp.int32),
        rate_starts=jnp.asarray(rs),
        rate_q16=jnp.asarray(rq),
        on_ticks=jnp.int32(acfg.on_ticks),
        cycle_ticks=jnp.int32(cycle),
        task_cost=jnp.int32(acfg.task_cost))


# --------------------------------------------------------------------------- #
# The candidate stream (pure functions of (seed, k) — the leap invariant)
# --------------------------------------------------------------------------- #
def stream_seed(seed):
    """Decorrelate the arrival stream from the victim-draw PRNG: a hashed
    uint32 substream seed derived from the run seed."""
    return tasks._hash2(jnp.asarray(seed, jnp.uint32), jnp.uint32(_SALT_SEED))


def _stream_u32(aseed, salt: int, k):
    s = tasks._hash2(aseed, jnp.uint32(salt))
    return tasks._hash2(s, jnp.asarray(k, jnp.uint32))


def gap_ticks(aseed, k, gap_q8):
    """Inter-candidate gap before candidate k: an exponential variate with
    mean ``gap_q8 / 256`` ticks, floored at 1 (at most one candidate per
    tick). float32 is deterministic here — the same elementwise graph runs
    in both step modes and in vmapped sweeps."""
    u = (_stream_u32(aseed, _SALT_GAP, k).astype(jnp.float32) + 1.0) \
        * jnp.float32(2.0**-32)                                   # (0, 1]
    g = -jnp.log(u) * jnp.asarray(gap_q8, jnp.float32) * jnp.float32(1 / 256)
    return jnp.clip(jnp.round(g), 1.0, float(1 << 29)).astype(jnp.int32)


def accepted(ar: ArrivalArrays, aseed, k, t):
    """Deterministic thinning of candidate k at its fire tick t: the
    per-epoch Q16 rate gate AND the burst on/off window."""
    u16 = (_stream_u32(aseed, _SALT_ACCEPT, k)
           & jnp.uint32(0xFFFF)).astype(jnp.int32)
    eidx = lstate.epoch_index(ar.rate_starts, t)
    thin_ok = u16 < ar.rate_q16[eidx]
    cyc = jnp.maximum(ar.cycle_ticks, 1)
    burst_ok = jnp.where(ar.cycle_ticks > 0, (t % cyc) < ar.on_ticks, True)
    return thin_ok & burst_ok


def station_of(ar: ArrivalArrays, aseed, k):
    """Ground station (worker id) of candidate k: a CDF inversion over the
    Zipf station weights (modulo draw — the ≤2^-31 modulo bias is far below
    any quantity measured here)."""
    u = _stream_u32(aseed, _SALT_STATION, k)
    total = ar.station_cdf[-1].astype(jnp.uint32)
    r = (u % total).astype(jnp.int32)
    return jnp.searchsorted(ar.station_cdf, r, side="right").astype(jnp.int32)


# --------------------------------------------------------------------------- #
# Load ↔ gap conversion + host-side oracle replay (tests)
# --------------------------------------------------------------------------- #


def host_arrival_schedule(seed: int, gap_q8: int, ar: ArrivalArrays,
                          max_ticks: int):
    """Pure-host replay of the candidate stream up to `max_ticks`: returns
    (ticks, stations, accepted) numpy arrays, one entry per candidate.
    Delegates to the jnp stream functions on scalars — host oracle and
    device stream can never disagree on float32 boundary cases."""
    aseed = stream_seed(seed)
    ticks, stations, accs = [], [], []
    t = int(gap_ticks(aseed, jnp.int32(0), jnp.int32(gap_q8)))
    k = 0
    while t < max_ticks:
        ticks.append(t)
        stations.append(int(station_of(ar, aseed, jnp.int32(k))))
        accs.append(bool(accepted(ar, aseed, jnp.int32(k), jnp.int32(t))))
        k += 1
        t += int(gap_ticks(aseed, jnp.int32(k), jnp.int32(gap_q8)))
    return (np.asarray(ticks, np.int64), np.asarray(stations, np.int64),
            np.asarray(accs, bool))
