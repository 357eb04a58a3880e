"""Flight recorder: on-device steal-attempt tracing + binned time series.

The simulator's end-of-run scalars (`attempts`, `successes`, total wait
ticks) say *how much* stealing happened, never *when* famine hit, *which*
links priced an attempt, or how imbalance evolved across eclipse / seam
epochs — yet per-attempt steal latency is the paper's central quantity
(§3.3 Eq. 1 prices a strategy by the distribution of attempt round trips).
This module records both views inside the simulator's `lax.while_loop`:

  * an **event ring** — a fixed-capacity SoA buffer of int32 lanes
    ``(tick, kind, worker, victim, hops, rtt_ticks, epoch)`` capturing every
    steal attempt with an outcome code plus the lifecycle events around
    them (deaths, wake-ups, link-state epoch flips, famine-window
    enter/exit, overflow drops). The emit counter `n` is monotonic and
    counts every event *including* the ones a full ring rejects, so
    ``dropped = max(n - capacity, 0)`` — truncation is never silent, and
    the drop counter is the ring-sizing guidance (re-run with a bigger
    ring until it reads 0);
  * a **binned time series** — a ``(bins, NUM_CHANNELS)`` scatter-add of
    per-interval busy worker-ticks, end-of-tick total queue depth,
    in-flight flight-ticks, attempts, successes, and alive worker-ticks
    (the busy-fraction denominator).

An unreachable-draw event (`EV_NO_LIVE_VICTIM`) is emitted only for
workers that *could* attempt under the current link state
(`simulator._can_attempt`): a fully victimless worker re-draws every tick
without an event.

Per-tick emission order (fixed, so rings compare elementwise): DEATH,
WAKE, EPOCH, NO_LIVE_VICTIM, ARRIVAL, SOJOURN, attempt resolutions
(SEVERED / EMPTY / GRANTED), OVERFLOW, FAMINE_ENTER / FAMINE_EXIT.
After the loop, attempts
still in their request flight emit one `EV_PENDING` each, making
``attempts == #resolved + #pending`` exact on runs without mid-flight
deaths (a death voids its thief's in-flight attempt — the DEATH event
marks it).

Under `Recovery.TC` the trace does NOT roll back with the snapshot (it is
an observability layer, like `hiwater`): the timeline keeps both the
discarded and the replayed attempts, and a rollback tick can contribute
*negative* busy/attempt deltas to its bin — that is the honest recording
of the rewind, identical in both step modes.

``SimConfig.trace`` is statically branched: with ``trace=None`` the
simulator never calls into this module and the compiled step graph is
bit-for-bit today's (asserted by the zero-overhead jaxpr test).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------- #
# Event schema
# --------------------------------------------------------------------------- #
# Steal-attempt outcome codes (one event per attempt, stamped at the tick
# the outcome is decided):
EV_NO_LIVE_VICTIM = 0   # drawn victim has no live route (other component):
                        # the flight never departs, no attempt is counted.
                        # Stamped at the draw tick; rtt = 0.
EV_EMPTY_VICTIM = 1     # request arrived, victim alive & reachable, but its
                        # deque was empty (or the per-round grant budget was
                        # exhausted). Stamped at the arrival tick.
EV_SEVERED_DENIAL = 2   # request arrived but no grant is possible: the
                        # victim died, or an epoch flip severed the reply
                        # path mid-flight (the thief waits out the nominal
                        # RTT as a timeout). Stamped at the arrival tick.
EV_GRANTED = 3          # request arrived and a bottom task was granted.
                        # Stamped at the arrival tick.
EV_PENDING = 4          # attempt still in its request flight when the run
                        # ended (counted in `attempts`, outcome unknown);
                        # rtt lane holds the request leg only.
# Lifecycle events (worker = the subject, victim = -1 unless noted):
EV_DEATH = 5            # scheduled failure / shutdown fired
EV_WAKE = 6             # eclipse exit: dead worker rejoined
EV_EPOCH = 7            # link-state epoch flip (worker = -1, epoch = new)
EV_FAMINE_ENTER = 8     # total stealable supply hit 0 (worker = -1)
EV_FAMINE_EXIT = 9      # supply became nonzero again (worker = -1)
EV_OVERFLOW = 10        # worker's deque rejected pushes this tick;
                        # rtt lane = number of records dropped
# Open-loop traffic events (see `core/arrivals.py`): together they form the
# per-task sojourn ledger — ARRIVAL stamps injection, SOJOURN stamps
# completion with the priced sojourn in the rtt lane.
EV_ARRIVAL = 11         # request injected at a ground station
                        # (worker = station, hops = task_id, rtt = 0)
EV_SOJOURN = 12         # request popped & served: rtt lane = sojourn ticks
                        # (pop_tick - inject_tick + service cost),
                        # victim = inject tick, hops = task_id

NUM_KINDS = 13
KIND_NAMES = {
    EV_NO_LIVE_VICTIM: "no_live_victim",
    EV_EMPTY_VICTIM: "empty_victim",
    EV_SEVERED_DENIAL: "severed_denial",
    EV_GRANTED: "granted",
    EV_PENDING: "pending",
    EV_DEATH: "death",
    EV_WAKE: "wake",
    EV_EPOCH: "epoch",
    EV_FAMINE_ENTER: "famine_enter",
    EV_FAMINE_EXIT: "famine_exit",
    EV_OVERFLOW: "overflow",
    EV_ARRIVAL: "arrival",
    EV_SOJOURN: "sojourn",
}
# attempt-kind events: one per steal attempt the thief resolved (or left
# pending); NO_LIVE_VICTIM draws never departed, so they are *not* part of
# the `attempts` counter reconciliation
RESOLVED_ATTEMPT_KINDS = (EV_EMPTY_VICTIM, EV_SEVERED_DENIAL, EV_GRANTED)
ATTEMPT_KINDS = RESOLVED_ATTEMPT_KINDS + (EV_PENDING,)

# Ring lanes (SoA columns of the (capacity, NUM_LANES) int32 buffer)
LANE_TICK = 0
LANE_KIND = 1
LANE_WORKER = 2   # the acting worker (thief for attempts)
LANE_VICTIM = 3   # attempt victim; -1 for lifecycle events
LANE_HOPS = 4     # nominal thief↔victim Manhattan hops (one-way); for
                  # EV_OVERFLOW: 0
LANE_RTT = 5      # priced round-trip ticks (request + response leg, incl.
                  # route-around detours); EV_OVERFLOW: records dropped
LANE_EPOCH = 6    # link-state epoch index at the stamp tick (0 if static)
NUM_LANES = 7

# Time-series channels
CH_BUSY = 0        # busy worker-ticks (burn or expand) per bin
CH_QUEUE = 1       # sum over ticks of end-of-tick total queue depth
CH_INFLIGHT = 2    # worker-ticks spent in REQ/RESP flights per bin
CH_ATTEMPTS = 3    # steal attempts launched per bin
CH_SUCCESSES = 4   # granted-loot deliveries per bin
CH_ALIVE = 5       # alive worker-ticks per bin (busy-fraction denominator)
NUM_CHANNELS = 6
CHANNEL_NAMES = ("busy", "queue_depth", "inflight", "attempts", "successes",
                 "alive")


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Static flight-recorder shape (part of the jit cache key via
    `SimConfig.trace`). `ring_capacity` bounds the event ring — size it
    from the reported drop counter (0 drops = complete trace). `bins` ×
    `bin_ticks` is the covered horizon; later ticks clamp into the last
    bin (int32 channels: keep `bin_ticks · W · capacity` < 2^31 so the
    queue-depth channel cannot wrap)."""
    ring_capacity: int = 4096
    bins: int = 256
    bin_ticks: int = 64

    def validate(self) -> "TraceConfig":
        if self.ring_capacity <= 0:
            raise ValueError("trace ring_capacity must be positive")
        if self.bins <= 0 or self.bin_ticks <= 0:
            raise ValueError("trace bins and bin_ticks must be positive")
        return self


class TraceState(NamedTuple):
    """Device-side recorder state, threaded through the simulator loop
    (OUTSIDE `SimState`, so TC snapshots never roll it back)."""
    ev: jax.Array         # (ring_capacity, NUM_LANES) int32 event ring
    n: jax.Array          # () int32 events emitted, incl. ring-dropped ones
    req_ticks: jax.Array  # (W,) int32 request-leg flight ticks of each
                          # worker's in-flight attempt (for the rtt lane)
    ts: jax.Array         # (bins, NUM_CHANNELS) int32 time series
    famine: jax.Array     # () bool end-of-tick famine flag (supply == 0)


def init(tcfg: TraceConfig, num_workers: int, famine0) -> TraceState:
    return TraceState(
        ev=jnp.full((tcfg.ring_capacity, NUM_LANES), -1, jnp.int32),
        n=jnp.int32(0),
        req_ticks=jnp.zeros((num_workers,), jnp.int32),
        ts=jnp.zeros((tcfg.bins, NUM_CHANNELS), jnp.int32),
        famine=jnp.asarray(famine0, bool))


def _rows(mask, tick, kind, worker, victim, hops, rtt, epoch):
    """Broadcast scalar-or-(K,) lanes to a (K, NUM_LANES) int32 block."""
    K = mask.shape[0]
    lanes = [tick, kind, worker, victim, hops, rtt, epoch]
    cols = [jnp.broadcast_to(jnp.asarray(x, jnp.int32), (K,)) for x in lanes]
    return jnp.stack(cols, axis=1)


def emit_raw(ev, n, capacity: int, mask, *, tick, kind, worker, victim,
             hops=0, rtt=0, epoch=0):
    """Core append on a bare (ring, counter) pair. One event per True lane
    of `mask` (worker-id
    order); events past `capacity` are counted but not written (their
    scatter rows are routed out of bounds, which XLA drops)."""
    mask = jnp.asarray(mask, bool)
    m32 = mask.astype(jnp.int32)
    slot = n + jnp.cumsum(m32) - m32                   # exclusive rank
    idx = jnp.where(mask & (slot < capacity), slot, capacity)
    ev = ev.at[idx].set(_rows(mask, tick, kind, worker, victim, hops,
                              rtt, epoch), mode="drop")
    return ev, n + jnp.sum(m32)


def emit(tr: TraceState, tcfg: TraceConfig, mask, *, tick, kind, worker,
         victim, hops=0, rtt=0, epoch=0) -> TraceState:
    """Append one event per True lane of `mask`, bumping the monotonic
    counter (drops counted, never silent)."""
    ev, n = emit_raw(tr.ev, tr.n, tcfg.ring_capacity, mask, tick=tick,
                     kind=kind, worker=worker, victim=victim, hops=hops,
                     rtt=rtt, epoch=epoch)
    return tr._replace(ev=ev, n=n)


def emit1(tr: TraceState, tcfg: TraceConfig, pred, *, tick, kind,
          worker=-1, victim=-1, hops=0, rtt=0, epoch=0) -> TraceState:
    """Append a single global event when `pred` holds (epoch flips, famine
    transitions)."""
    return emit(tr, tcfg, jnp.reshape(jnp.asarray(pred, bool), (1,)),
                tick=tick, kind=kind, worker=worker, victim=victim,
                hops=hops, rtt=rtt, epoch=epoch)


def ts_add(tr: TraceState, tcfg: TraceConfig, t, *, busy, queue, inflight,
           attempts, successes, alive) -> TraceState:
    """Scatter-add one tick's contribution into the bin containing `t`."""
    b = jnp.minimum(t // tcfg.bin_ticks, tcfg.bins - 1)
    row = jnp.stack([jnp.asarray(x, jnp.int32) for x in
                     (busy, queue, inflight, attempts, successes, alive)])
    return tr._replace(ts=tr.ts.at[b].add(row))


# --------------------------------------------------------------------------- #
# Host-side views
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class Trace:
    """Finalized event ring: `events` is the (n_written, NUM_LANES) int32
    array in emission order; `emitted` counts every event including the
    `dropped` ones a full ring rejected (size the ring until dropped == 0)."""
    events: np.ndarray
    emitted: int
    dropped: int
    ring_capacity: int

    def lane(self, lane: int) -> np.ndarray:
        return self.events[:, lane]

    def of_kind(self, *kinds: int) -> np.ndarray:
        sel = np.isin(self.events[:, LANE_KIND], kinds)
        return self.events[sel]

    def counts(self) -> dict[str, int]:
        k = self.events[:, LANE_KIND]
        return {name: int((k == kind).sum())
                for kind, name in KIND_NAMES.items()}


@dataclasses.dataclass(frozen=True)
class TimeSeries:
    """Finalized (bins, NUM_CHANNELS) time series (int64 host copy)."""
    data: np.ndarray
    bin_ticks: int

    def channel(self, ch: int) -> np.ndarray:
        return self.data[:, ch]

    def busy_fraction(self) -> np.ndarray:
        alive = np.maximum(self.data[:, CH_ALIVE], 1)
        return self.data[:, CH_BUSY] / alive

    def mean_queue_depth(self) -> np.ndarray:
        """Per-bin mean end-of-tick total queue depth. The queue channel
        sums one constellation-wide total per simulated tick; dividing by
        `bin_ticks` gives the per-tick mean (edge bins of a run that ends
        mid-bin read proportionally low)."""
        return self.data[:, CH_QUEUE] / float(self.bin_ticks)


def finalize(tr, tcfg: TraceConfig) -> tuple[Trace, TimeSeries]:
    """Build host-side views from a device-fetched `TraceState`."""
    emitted = int(tr.n)
    written = min(emitted, tcfg.ring_capacity)
    events = np.asarray(tr.ev)[:written]
    return (Trace(events=events, emitted=emitted,
                  dropped=max(emitted - tcfg.ring_capacity, 0),
                  ring_capacity=tcfg.ring_capacity),
            TimeSeries(data=np.asarray(tr.ts, np.int64),
                       bin_ticks=tcfg.bin_ticks))


# --------------------------------------------------------------------------- #
# Perfetto / Chrome-trace export
# --------------------------------------------------------------------------- #
def sojourn_stats(trace: Trace) -> dict | None:
    """Tail-latency percentiles of every completed request in the ring.

    Each `EV_SOJOURN` event carries one request's sojourn (queue wait +
    nominal service, in ticks) in the rtt lane. Returns nearest-rank
    p50/p90/p99/p999 plus count/mean/max — the SLO quantities of the
    load–latency study — or None when the ring holds no completions.
    Percentiles are exact order statistics of the *recorded* events; size
    the ring until `trace.dropped == 0` for exact run-level numbers."""
    soj = np.sort(trace.of_kind(EV_SOJOURN)[:, LANE_RTT].astype(np.int64))
    n = int(soj.size)
    if n == 0:
        return None
    rank = lambda p: int(soj[max(int(np.ceil(p / 100.0 * n)), 1) - 1])
    return dict(count=n, p50=rank(50), p90=rank(90), p99=rank(99),
                p999=rank(99.9), mean=float(soj.mean()), max=int(soj[-1]))
