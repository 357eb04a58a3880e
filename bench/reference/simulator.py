"""Tick-level simulator of work stealing on a high-latency 2D mesh.

The paper's experiments run on a *uniform low-latency* HPC interconnect and
leave "empirical evaluation on an emulated high-latency mesh" as future work
(§6). This module builds that emulation: a vectorized, deterministic,
tick-stepped model of the constellation where

  * one tick = one work unit of task execution;
  * each mesh hop costs `hop_ticks` ticks (τ in work-unit currency), so a
    neighbor-only steal attempt occupies the thief for 2·hop_ticks ticks and
    a global steal for 2·hops(thief,victim)·hop_ticks ticks — assumptions
    (i)–(iii) of §3.3, executed rather than integrated;
  * steal requests resolve at *arrival* time: a victim serves the requests
    that arrive in the same tick in deterministic priority order, granting
    one bottom task each while tasks last (§3.1 step 3-4: a failed attempt
    sends the thief straight back to victim selection).

This module is the benchmark's reference: the model above stepped one tick
per loop iteration, every deque op committed as its own scatter, and the
victim-side export done in plain jnp. It keeps none of the program's speed
machinery (event leaping, the famine-window replay, the staged deque
backend, the Pallas kernels, the vmapped and sharded sweep entries), so a
fault in any of them cannot pass the comparison by being shared. Every
statistic of a `SimResult` but `events` (the loop's iteration count, equal
to `ticks` here) is defined by the per-tick model alone.

Beyond the paper's model, the simulator covers the SEC failure modes the
paper lists in §2.1/§5, each as an orthogonal mechanism:

  * **failures** — a schedule kills workers at given ticks (radiation, power
    loss). Recovery options:
      - ``Recovery.TC``: coordinated task-level checkpointing every
        `ckpt_interval` ticks; on failure the constellation rolls back to the
        last snapshot and the dead worker's snapshot deque + accumulator are
        transplanted to its nearest live mesh neighbor.
      - ``Recovery.SUPERVISION``: every victim remembers the tasks stolen
        from it (ring buffer of `supervision_slots`); when a thief dies its
        victims re-push the un-acknowledged records.
      - ``Recovery.NONE``: lost work stays lost.
  * **malleability** — predictable shutdowns (battery/eclipse) give a
    `warn_ticks` lead; the doomed worker *pre-sheds*, pushing its entire
    deque and accumulator to live neighbors before sleeping.
  * **stragglers** — per-worker `speed` divisors (a speed-s worker advances
    work only every s-th tick).
  * **time-varying link state** — a `linkstate.LinkStateSchedule`: per-epoch
    per-link τ, link up/down intervals masking radius-1 victim sets, and
    per-epoch straggler speeds. Flights are priced by dimension-order path
    sums at the departure epoch, or along the epoch's live-link detours
    where a link is down; fully partitioned victims are unreachable.
  * **open-loop traffic** (`arrivals.py`) — ground stations inject
    `tasks.KIND_REQ` request records; per-request sojourns accumulate into
    `SimResult.sojourn_sum_ticks` / `requests_done`, and with the recorder
    on, the ring yields the sojourn percentiles (`SimResult.sojourn`).
  * **wake-ups** — `wake_time`: a dead worker rejoins at its wake tick with
    a fresh, empty state (eclipse exits).

Congestion accounting: every steal message contributes payload_bytes × hops
to `bytes_hops`, in an exact 62-bit integer (a pair of int32 lanes with
explicit carry).
"""

from __future__ import annotations

import dataclasses
import enum
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import arrivals
from . import deque as dq
from . import linkstate as lstate
from . import stealing, tasks
from . import topology as topo
from . import tracing

PHASE_RUN = 0
PHASE_REQ = 1   # steal request in flight (thief → victim)
PHASE_RESP = 2  # steal response in flight (victim → thief)

STEAL_MSG_BYTES = 32  # request+reply payload estimate (task record + header)

# Exact hop accounting: low lane holds 30 bits, high lane the carries.
_HOP_LANE_BITS = 30
_HOP_LANE_MASK = (1 << _HOP_LANE_BITS) - 1

# Next-event sentinel: beyond any reachable tick (max_ticks is asserted
# smaller), safe to take min/clip against without int32 overflow.
_NEVER = jnp.int32(1 << 30)


class Recovery(enum.Enum):
    NONE = "none"
    TC = "tc"
    SUPERVISION = "supervision"


@dataclasses.dataclass(frozen=True)
class SimConfig:
    strategy: stealing.Strategy = stealing.Strategy.NEIGHBOR
    hop_ticks: int = 5                 # τ in work-unit ticks
    capacity: int = 1024
    max_grants_per_victim: int = 4     # per-round budget, <= stealing.GRANT_WIDTH
    escalate_after: int = 4
    max_ticks: int = 2_000_000
    seed: int = 0
    # fault tolerance
    recovery: Recovery = Recovery.NONE
    ckpt_interval: int = 0             # TC: ticks between snapshots (0 = off)
    supervision_slots: int = 64
    warn_ticks: int = 0                # malleability: pre-shed lead time
    preshed: bool = False
    # open-loop traffic (core/arrivals.py): mean inter-candidate gap in
    # Q8.8-style fixed point (mean gap ticks × 256; 0 = closed system — no
    # arrivals) and request records injected per accepted candidate
    # (1..arrivals.ARRIVAL_K). Both are traced sweep axes: an offered-load
    # sweep reuses ONE compilation. The arrival *shape* (stations, burst
    # windows, per-epoch rate schedule) travels separately via the
    # `arrivals=` argument of simulate/simulate_batch/simulate_sweep.
    arrival_gap_q8: int = 0
    arrival_batch: int = 1
    # flight recorder (core/tracing.py): None = off — statically branched,
    # so the disabled path compiles to exactly the untraced step graph
    # A `tracing.TraceConfig` turns on the in-loop event ring + binned
    # time series.
    trace: "tracing.TraceConfig | None" = None

    @property
    def static(self) -> "StaticConfig":
        """The static (shape/program-structure) half — the jit cache key."""
        return StaticConfig(
            capacity=self.capacity, max_ticks=self.max_ticks,
            recovery=self.recovery,
            supervision_slots=self.supervision_slots, preshed=self.preshed,
            trace=self.trace)

    @property
    def params(self) -> "SimParams":
        """The traced half — the sweep axes, as an int32-leaved pytree."""
        return SimParams(
            strategy=stealing.strategy_code(self.strategy),
            hop_ticks=self.hop_ticks, escalate_after=self.escalate_after,
            max_grants_per_victim=self.max_grants_per_victim,
            warn_ticks=self.warn_ticks, ckpt_interval=self.ckpt_interval,
            seed=self.seed, arrival_gap_q8=self.arrival_gap_q8,
            arrival_batch=self.arrival_batch)

    def split(self) -> "tuple[StaticConfig, SimParams]":
        return self.static, self.params


@dataclasses.dataclass(frozen=True)
class StaticConfig:
    """The static half of a `SimConfig`: only fields that determine array
    shapes or program structure. Hashable — the jit static argument — so
    ONE XLA compilation per distinct `StaticConfig` serves every `SimParams`
    point of a sweep grid (compile-count pinned in tests). Field semantics
    are documented on `SimConfig`, the user-facing combined view."""
    capacity: int = 1024
    max_ticks: int = 2_000_000
    recovery: Recovery = Recovery.NONE
    supervision_slots: int = 64
    preshed: bool = False
    trace: "tracing.TraceConfig | None" = None


class SimParams(NamedTuple):
    """The traced half of a `SimConfig`: the sweep axes. Every leaf is an
    int (or int32 scalar array; (G,)-stacked vectors in grid runs — see
    `stack_params` / `simulate_sweep`). Changing any leaf re-EXECUTES the
    compiled simulator; it never retraces it. The strategy travels as its
    `stealing.*_CODE` int, dispatched inside the core with `lax.switch`."""
    strategy: int = stealing.NEIGHBOR_CODE
    hop_ticks: int = 5
    escalate_after: int = 4
    max_grants_per_victim: int = 4
    warn_ticks: int = 0
    ckpt_interval: int = 0
    seed: int = 0
    arrival_gap_q8: int = 0
    arrival_batch: int = 1


class SimState(NamedTuple):
    deque: dq.DequeState
    acc: jax.Array          # (W,) int32 mod-RESULT_MOD checksum
    work: jax.Array         # (W,) int32 remaining ticks on current expansion
    fails: jax.Array        # (W,) consecutive failed attempts
    phase: jax.Array        # (W,) PHASE_*
    timer: jax.Array        # (W,) ticks left in current phase
    victim: jax.Array       # (W,) in-flight victim id
    loot: jax.Array         # (W, T) in-flight stolen record
    got: jax.Array          # (W,) bool steal granted (valid in PHASE_RESP)
    alive: jax.Array        # (W,) bool
    # supervision: record stolen (task, thief) pairs per victim
    sup_buf: jax.Array      # (W, S, T) stolen records
    sup_thief: jax.Array    # (W, S) thief ids (-1 = empty slot)
    sup_n: jax.Array        # (W,) write cursor
    # stats
    attempts: jax.Array
    successes: jax.Array
    nodes: jax.Array
    busy: jax.Array         # (W,) ticks spent working
    steal_wait: jax.Array   # (W,) ticks spent in REQ/RESP
    hops_lo: jax.Array      # () int32: Σ msg hops, low 30-bit lane (exact)
    hops_hi: jax.Array      # () int32: Σ msg hops, carry lane
    ckpt_count: jax.Array   # () int32 checkpoints taken
    overflow: jax.Array     # (W,) int32 dropped-task count per worker: counts
                            # every push that found a full deque — expansion
                            # children, thief-side loot imports, transplant
                            # writes (charged to the heir), supervision
                            # re-pushes — so no loss is ever silent
    stolen_from: jax.Array  # (W,) int32 tasks granted out of each worker's
                            # deque bottom (victim-side view of successful
                            # steals, counted at grant time)
    hiwater: jax.Array      # (W,) int32 running max end-of-tick deque
                            # occupancy (victim-side) — sizes capacity for
                            # W >= 4k sweeps; mid-tick transients that were
                            # rejected show up in `overflow` instead
    # open-loop arrival stream (core/arrivals.py). The cursor is EXTERNAL
    # input state — excluded from TC rollback (see apply_tc): rolling arr_t
    # back below the clock would leave a candidate tick that never fires
    # again and stall the stream forever.
    arr_t: jax.Array        # () int32 next candidate's fire tick
                            # (_NEVER = stream off / exhausted)
    arr_k: jax.Array        # () int32 candidate-stream cursor
    arr_injected: jax.Array # () int32 request records injected into deques
    arr_dropped: jax.Array  # () int32 request records lost at injection
                            # (full or dead station deque — never silent)
    arr_done: jax.Array     # () int32 requests completed (popped & served)
    soj_lo: jax.Array       # () int32 Σ sojourn ticks, low 30-bit lane
    soj_hi: jax.Array       # () int32 Σ sojourn ticks, carry lane


class SimResult(NamedTuple):
    result: int
    ticks: int
    nodes: int
    attempts: int
    successes: int
    p_success: float
    busy_ticks: int
    steal_wait_ticks: int
    bytes_hops: float
    ckpt_bytes: float
    overflow: int
    utilization: float
    per_worker_busy: np.ndarray
    # loop iterations executed (== ticks here: one tick per iteration)
    events: int = 0
    # (W,) breakdown of `overflow`: dropped tasks charged to the worker whose
    # full deque rejected the push (thief-side loot imports included)
    per_worker_overflow: np.ndarray | None = None
    # (W,) tasks granted out of each worker's deque bottom (victim-side
    # steal count) — lets tests pin *who* was stolen from, e.g. that a
    # woken worker rejoined the victim set after an eclipse exit
    per_worker_stolen: np.ndarray | None = None
    # (W,) running max END-OF-TICK deque occupancy (hiwater <= capacity
    # always; survives TC rollbacks — the buffers physically held the
    # peak). A capacity floor for sizing W >= 4k runs from a pilot, but
    # note it does not see mid-tick transients (children are pushed
    # before grants export within a tick), so the actual certificate for
    # a chosen capacity is the re-run reporting overflow == 0
    per_worker_hiwater: np.ndarray | None = None
    # (W,) per-worker ledgers behind the scalar `attempts` / `successes`:
    # steal attempts launched by each thief (counted at request departure)
    # and granted-loot deliveries received (counted at response delivery).
    # Cross-checked against trace-ring sums in tests when tracing is on.
    per_worker_attempts: np.ndarray | None = None
    per_worker_successes: np.ndarray | None = None
    # flight recorder output (None unless cfg.trace is set): the finalized
    # event ring and the (bins, channels) binned time series
    trace: "tracing.Trace | None" = None
    timeseries: "tracing.TimeSeries | None" = None
    # open-loop traffic ledger (zeros on closed runs): records injected /
    # lost at injection / completed, and the exact 62-bit sojourn-tick sum
    # over completed requests (sojourn = pop_tick − inject_tick + cost)
    arrivals_injected: int = 0
    arrivals_dropped: int = 0
    requests_done: int = 0
    sojourn_sum_ticks: int = 0
    sojourn_mean: float = 0.0
    # nearest-rank sojourn percentiles from the trace ring (requires
    # cfg.trace; see `tracing.sojourn_stats`): dict with count / p50 /
    # p90 / p99 / p999 / mean / max, or None when untraced / no
    # completions. Exact over the recorded events — size the ring until
    # trace.dropped == 0 for exact run-level percentiles.
    sojourn: dict | None = None


def _mesh_tables(mesh: topo.MeshTopology):
    """Static lookup tables for EVERY strategy — the strategy is a traced
    `SimParams` leaf, so the compiled program must be able to select any of
    them. All tables are (W, ≤12ish) int32 — a few hundred KB at W=16384.

    Hop distances are computed on the fly from (W, 2) coordinates — the
    dense (W, W) hop matrix is never built, so W >= 4k meshes don't embed
    multi-MB constants in the graph.
    """
    return {
        "neighbors": jnp.asarray(stealing.neighbor_list(mesh)),
        "coords": jnp.asarray(mesh.coords),
        "radius2": jnp.asarray(stealing.radius2_list(mesh)),
        "lifelines": jnp.asarray(stealing.lifeline_list(mesh.num_workers)),
    }


# Per-worker hop distances are priced from coordinates (topology.hop_dist);
# no dense pairwise table ever enters the per-tick path.
_hop_dist = topo.hop_dist


def _masked_radius2(tbl, ls, eidx):
    """ADAPTIVE's escalated victim set under the active epoch's link state:
    radius-2 entries in a different live-link component are unreachable —
    the escalated draw must not waste picks on them (and the famine
    predicate may not treat them as reachable supply). A (W, 12) gather
    from the per-epoch component row; the unmasked table when the schedule
    has no outage epochs (trace-time: no outage tables of either routing
    backend)."""
    r2 = tbl.get("radius2")
    if r2 is None or ls is None or not lstate.has_outage_tables(ls):
        return r2
    return stealing.mask_reachable(r2, ls.comp[eidx])


def _select(code, escalate_after, tbl, key, is_thief, fails, W, link=None):
    """Victim selection, dispatched over the traced strategy `code` with
    `lax.switch` (branch order == the `stealing.*_CODE` order). Each branch
    calls the same `choose_*` function, with the same key usage, as the
    per-strategy path always did — a sweep-grid run therefore draws the
    exact victim sequence of a dedicated compile. `link = (up_row, tau_row,
    r2_masked)` masks radius-1 victim sets with the active epoch's link
    state and restricts ADAPTIVE's escalated set to reachable (same
    live-link component) victims. GLOBAL / LIFELINE draw over all workers;
    the caller gates their flight *departures* on reachability instead (an
    unreachable draw never launches — see linkstate module docstring)."""
    if link is None:
        nbrs, tau_row, r2m = tbl["neighbors"], None, tbl["radius2"]
    else:
        up_row, tau_row, r2m = link
        nbrs = jnp.where(up_row & (tbl["neighbors"] >= 0), tbl["neighbors"],
                         topo.NO_NEIGHBOR)

    def b_global(_):
        return stealing.choose_global(key, W, is_thief)

    def b_neighbor(_):
        return stealing.choose_neighbor(key, nbrs, is_thief)

    def b_lifeline(_):
        return stealing.choose_lifeline(key, tbl["lifelines"], fails, W,
                                        is_thief)

    def b_adaptive(_):
        if link is None:
            return stealing.choose_adaptive(key, nbrs, r2m, fails, is_thief,
                                            escalate_after)
        return stealing.choose_adaptive_linkaware(key, nbrs, r2m, tau_row,
                                                  fails, is_thief,
                                                  escalate_after)

    return jax.lax.switch(code, [b_global, b_neighbor, b_lifeline,
                                 b_adaptive], None)


def _nearest_alive_neighbor(tbl, alive, w_dead):
    """For each dead worker, pick its first live mesh neighbor (or worker 0)."""
    nbrs = tbl["neighbors"]  # (W, 4)
    W = nbrs.shape[0]
    valid = (nbrs >= 0) & alive[jnp.clip(nbrs, 0, W - 1)]
    first = jnp.argmax(valid, axis=1)
    heir = jnp.where(valid.any(axis=1), nbrs[jnp.arange(W), first], 0)
    return heir


def _transplant_plan(size, src_mask, heir, cap: int):
    """Append plan: where every transplanted record lands on its heir,
    which records the heir's capacity rejects, and the per-worker size
    delta.

    Heir h receives all tasks of its dead sources, sequentially. Multiple
    sources per heir are handled by offsetting each source with the summed
    counts of its heir's earlier (lower worker id) sources — a sorted
    segment prefix, no (W, W) pairwise matrix.
    """
    W = size.shape[0]
    ranks = jnp.arange(cap)[None, :]
    src_counts = jnp.where(src_mask, size, 0)
    offset = stealing.segment_prefix(heir, src_mask, src_counts)
    live = src_mask[:, None] & (ranks < src_counts[:, None])
    # drop writes that would overflow the heir; charge drops to the heir
    # whose capacity rejected them (per-worker breakdown in SimResult)
    room = cap - size[heir] - offset
    fits = ranks < room[:, None]
    write = live & fits
    dropped = jnp.sum(live & ~fits, axis=1).astype(jnp.int32)
    written = jnp.sum(write, axis=1).astype(jnp.int32)
    added = jnp.zeros((W,), jnp.int32).at[heir].add(
        jnp.where(src_mask, written, 0))
    return ranks, offset, write, dropped, added


def _transplant_acc(acc, src_mask, heir):
    new_acc = acc.at[heir].add(jnp.where(src_mask, acc, 0))
    return jnp.where(src_mask, 0, new_acc) % tasks.RESULT_MOD


def _transplant(deque_, acc, src_mask, heir, overflow):
    """Move every `src_mask` worker's deque + acc onto its heir, emptying src.

    Vectorized one-source-at-a-time via scan over workers would be O(W·C);
    instead we exploit that heirs are (nearly) idle during recovery and
    append src rings onto heir rings with a bounded copy of `cap` slots.
    """
    W, cap, T = deque_.buf.shape
    src_tasks = dq.peek_bottom_window(deque_, cap)          # (W, cap, T)
    ranks, offset, write, dropped, added = _transplant_plan(
        deque_.size, src_mask, heir, cap)
    overflow = overflow.at[heir].add(jnp.where(src_mask, dropped, 0))
    buf, bot, size = deque_.buf, deque_.bot, deque_.size
    heir_base = size[heir] + offset                        # insertion cursor per source
    dst_slot = (bot[heir][:, None] + heir_base[:, None] + ranks) % cap
    # Scatter with duplicate (row, slot) pairs is order-undefined in XLA:
    # inactive rows must NOT read-modify-write the same destinations (a
    # no-op write may clobber a real one). Route every inactive element
    # out of bounds instead — XLA scatter drops them.
    dst_w = jnp.where(write, jnp.broadcast_to(heir[:, None], (W, cap)), W)
    buf = buf.at[dst_w, dst_slot].set(src_tasks, mode="drop")
    size = jnp.where(src_mask, 0, size + added)
    return (dq.DequeState(buf, bot, size),
            _transplant_acc(acc, src_mask, heir), overflow)


class _LoopDeques:
    """Per-op deques: every mutation commits its own `(W, C, T)` buffer
    update."""

    def __init__(self, state: dq.DequeState):
        self.st = state

    @property
    def size(self):
        return self.st.size

    def push(self, task, mask):
        self.st, ok = dq.push_top(self.st, task, mask)
        return ok

    def push_many(self, tasks_, counts):
        self.st, over = dq.push_top_many(self.st, tasks_, counts)
        return over

    def pop(self, mask):
        self.st, task, ok = dq.pop_top(self.st, mask)
        return task, ok

    def export(self, grants, width):
        stolen, self.st = dq.export_bottom(self.st, grants, width)
        return stolen

    def clear(self, mask):
        self.st = dq.DequeState(self.st.buf, self.st.bot,
                                jnp.where(mask, 0, self.st.size))

    def select(self, pred, other: dq.DequeState):
        self.st = jax.tree.map(lambda o, c: jnp.where(pred, o, c),
                               other, self.st)

    def transplant(self, acc, src_mask, heir, overflow):
        self.st, acc, overflow = _transplant(self.st, acc, src_mask, heir,
                                             overflow)
        return acc, overflow

    def finish(self) -> dq.DequeState:
        return self.st


def _epoch_view(ls, t):
    """(epoch index, per-worker speed row) of the epoch containing tick t."""
    eidx = lstate.epoch_index(ls.epoch_starts, t)
    return eidx, ls.speed[eidx]


def _can_attempt(code, escalate_after, tbl, ls, eidx, fails, W: int):
    """Per-worker: could an idle thief launch a steal flight right now?

    Radius-1 strategies lose victims when every adjacent link is down
    (eclipse / handover outage); multi-hop strategies lose them only when
    no *reachable* other worker exists (live-link partition — their draws
    toward other components never depart). Must never be False when
    `_select` + the departure gate could produce a flight. The strategy is a
    traced `code`: every variant is computed (cheap row reductions) and the
    code-selected one returned, each matching its dedicated-strategy
    formula bit-for-bit.
    """
    if ls is None or not lstate.has_outage_tables(ls):
        # multi-hop (GLOBAL / LIFELINE) capability without outage epochs:
        # any other worker will do
        multi = jnp.broadcast_to(jnp.bool_(W > 1), (W,))
    else:
        c = ls.comp[eidx]
        comp_size = jnp.zeros((W,), jnp.int32).at[c].add(1)
        multi = comp_size[c] > 1
    if ls is None:
        # no schedule: radius-1 sets are never masked, so every strategy
        # reduces to "another worker exists"
        return multi
    nbr_live = (ls.link_up[eidx] & (tbl["neighbors"] >= 0)).any(axis=1)
    # ADAPTIVE: escalated thieves fall back to the reachability-masked
    # radius-2 set (all entries masked away ⇒ no escalated victim either)
    r2m = _masked_radius2(tbl, ls, eidx)
    r2_any = (r2m != topo.NO_NEIGHBOR).any(axis=1)
    adaptive = nbr_live | (r2_any & (fails >= escalate_after))
    return jnp.where(code == stealing.NEIGHBOR_CODE, nbr_live,
                     jnp.where(code == stealing.ADAPTIVE_CODE, adaptive,
                               multi))


def _fires_now(base, period, t):
    """Does the periodic event anchored at `base` with cycle `period` fire
    at tick t?  period == -1 is the one-shot (scalar schedule) case, where
    this reduces bit-exactly to ``base == t``; period > 0 fires at
    ``base + k * period`` for every k >= 0. `base < 0` never fires."""
    hit = jnp.where(period > 0,
                    (t - base) % jnp.maximum(period, 1) == 0,
                    t == base)
    return (base >= 0) & (t >= base) & hit


def _next_fire(base, period, t):
    """First fire tick >= t of the periodic event (base, period); `_NEVER`
    when none remains. One-shot (period == -1) reduces bit-exactly to the
    scalar horizon terms: base if still pending, else `_NEVER`. Int32-safe
    for period < 2**29 (validated host-side) and t <= max_ticks < 2**30."""
    pp = jnp.maximum(period, 1)
    k = jnp.maximum((t - base + pp - 1) // pp, 0)
    periodic = base + k * pp
    one_shot = jnp.where(base >= t, base, _NEVER)
    return jnp.where(base < 0, _NEVER,
                     jnp.where(period > 0, periodic, one_shot))


def _retired_mask(cfg: StaticConfig, warn_ticks, fail_time, fail_period, t,
                  W: int):
    """Pre-shed retirement: a warned worker idles from `fail - warn_ticks`
    until its (predictable) death and must not pull work back in. Phrased
    on the NEXT pending death: an alive worker is retired iff a death fire
    is due within `warn_ticks` — so a worker that rejoined after an
    eclipse exit is a full citizen again (its next fire is a full cycle
    out), and one-shot schedules reduce bit-exactly to the scalar rule for
    every alive worker (the only consumers — dead workers never read it)."""
    if not cfg.preshed:
        return jnp.zeros((W,), bool)
    nf = _next_fire(fail_time, fail_period, t)
    return (nf < _NEVER) & (t >= nf - warn_ticks)


def _sim_core(workload, mesh: topo.MeshTopology, cfg: StaticConfig,
              p: SimParams, fail_time, wake_time, fail_period, speed,
              ls=None, ar=None):
    W = mesh.num_workers
    torus_full = mesh.torus_full()
    tbl = _mesh_tables(mesh)
    tables = workload.tables()
    S = cfg.supervision_slots
    code, escalate_after = p.strategy, p.escalate_after
    key0 = jax.random.PRNGKey(p.seed)

    deques = dq.make(W, cfg.capacity)
    T = deques.buf.shape[2]  # task record width — single source of truth
    root = jnp.asarray(workload.root_task())
    assert root.shape[-1] == T, (
        f"root task width {root.shape[-1]} != deque record width {T}")
    deques, _ = dq.push_top(deques, jnp.broadcast_to(root[None], (W, T)),
                            jnp.arange(W) == 0)

    def _session(deq):
        # one scatter per deque op
        return _LoopDeques(deq)

    # open-loop arrival stream: the first candidate's fire tick. The whole
    # stream is a pure function of (seed, candidate index) — see
    # core/arrivals.py — so the carried cursor (arr_t, arr_k) is the ONLY
    # stream state.
    # arrival_gap_q8 == 0 (the traced "closed system" point) parks the
    # cursor at _NEVER: same compiled graph, no candidate ever fires.
    if ar is not None:
        aseed = arrivals.stream_seed(p.seed)
        arr_t0 = jnp.where(
            p.arrival_gap_q8 > 0,
            jnp.minimum(arrivals.gap_ticks(aseed, jnp.int32(0),
                                           p.arrival_gap_q8), _NEVER),
            _NEVER)
    else:
        aseed = None
        arr_t0 = _NEVER

    z = jnp.zeros((W,), jnp.int32)
    state0 = SimState(
        deque=deques, acc=z, work=z, fails=z,
        phase=z, timer=z, victim=z - 1, loot=jnp.zeros((W, T), jnp.int32),
        got=jnp.zeros((W,), bool), alive=jnp.ones((W,), bool),
        sup_buf=jnp.zeros((W, S, T), jnp.int32),
        sup_thief=jnp.full((W, S), -1, jnp.int32), sup_n=z,
        attempts=z, successes=z, nodes=z, busy=z, steal_wait=z,
        hops_lo=jnp.int32(0), hops_hi=jnp.int32(0),
        ckpt_count=jnp.int32(0), overflow=z, stolen_from=z,
        hiwater=deques.size,
        arr_t=jnp.asarray(arr_t0, jnp.int32), arr_k=jnp.int32(0),
        arr_injected=jnp.int32(0), arr_dropped=jnp.int32(0),
        arr_done=jnp.int32(0), soj_lo=jnp.int32(0), soj_hi=jnp.int32(0))

    # flight recorder: () when disabled — every emission site below sits
    # behind a static `if trc is not None`, so the untraced while_loop body
    # is exactly the pre-trace graph. The recorder rides the loop carry
    # OUTSIDE SimState: TC rollbacks restore the snapshot, but the trace is
    # an observability layer (like `hiwater`) and must keep the discarded
    # timeline.
    trc = cfg.trace
    tr0 = (tracing.init(trc, W, jnp.sum(deques.size) == 0)
           if trc is not None else ())

    def tick_fn(carry):
        state, snap, tr, t = carry
        st_in = state  # entry state: the tick's time-series deltas baseline
        key = jax.random.fold_in(key0, t)
        alive = state.alive
        if ls is None:
            eidx, sp, link = None, speed, None
        else:
            eidx, sp = _epoch_view(ls, t)
            link = (ls.link_up[eidx], ls.link_tau[eidx],
                    _masked_radius2(tbl, ls, eidx))

        # ------------- scheduled failures / shutdowns --------------------- #
        # periodic schedules fire at base + k·period (one-shot: base == t)
        dying_now = alive & _fires_now(fail_time, fail_period, t)
        warned = (alive & _fires_now(fail_time, fail_period,
                                     t + p.warn_ticks)
                  if cfg.preshed else jnp.zeros((W,), bool))

        # every deque mutation below goes through the session, which
        # commits op by op. `state.deque` is stale until ses.finish() lands
        # in new_state.
        ses = _session(state.deque)

        # malleable pre-shed: migrate whole deque+acc one warn window early,
        # then a final flush at the (predictable) death tick catches any loot
        # delivered in between. Retired workers stop stealing (see below).
        acc, overflow = state.acc, state.overflow
        if cfg.preshed:
            heir = _nearest_alive_neighbor(tbl, alive & ~warned & ~dying_now,
                                           jnp.arange(W))
            acc, overflow = ses.transplant(acc, warned, heir, overflow)
            # death-tick flush: bank in-flight loot into own deque, then move all
            flush = dying_now
            want_bank = flush & state.got
            banked = ses.push(state.loot, want_bank)
            overflow = overflow + (want_bank & ~banked).astype(jnp.int32)
            acc, overflow = ses.transplant(acc, flush, heir, overflow)
            state = state._replace(got=jnp.where(flush, False, state.got))

        state = state._replace(acc=acc, overflow=overflow)

        # apply deaths
        alive = alive & ~dying_now

        def apply_tc(state, snap):
            # Roll the whole constellation back to the last coordinated
            # snapshot (a consistent cut — in-flight steal state is part of
            # it and is restored verbatim), then transplant the dead
            # worker's snapshot deque + accumulator + in-flight loot onto
            # its heir. Exactly-once for arbitrary failure schedules.
            rb = dying_now.any() & (p.ckpt_interval > 0)
            # the session owns the live deque: on rollback it resets to
            # the snapshot, mirroring the wholesale merge below
            ses.select(rb, snap.deque)
            merged = jax.tree.map(lambda s, c: jnp.where(rb, s, c), snap,
                                  state._replace(deque=snap.deque))
            heir = _nearest_alive_neighbor(tbl, alive, jnp.arange(W))
            # the snapshot may predate EARLIER deaths, resurrecting state on
            # long-dead workers — transplant everything on ANY dead worker
            dead = (~alive) & rb
            # bank the dead worker's in-flight loot into its own deque first
            want_bank = dead & merged.got
            banked = ses.push(merged.loot, want_bank)
            ovf = merged.overflow + (want_bank & ~banked).astype(jnp.int32)
            acc, ovf = ses.transplant(merged.acc, dead, heir, ovf)
            return merged._replace(
                acc=acc, overflow=ovf, alive=alive,
                # only the DEAD workers' in-flight state is voided
                phase=jnp.where(dead, 0, merged.phase),
                timer=jnp.where(dead, 0, merged.timer),
                work=jnp.where(dead, 0, merged.work),
                got=jnp.where(dead, False, merged.got),
                # the occupancy high-water mark is an observability
                # counter, not simulation state: the discarded ticks
                # physically filled the buffers, so a rollback must not
                # erase the peak (capacity sized to the reported hiwater
                # has to fit the PRE-rollback segment on a re-run too)
                hiwater=state.hiwater,
                # the arrival stream is EXTERNAL input, not simulation
                # state: restoring a snapshot cursor would put arr_t in
                # the past, where `t == arr_t` never fires again and the
                # stream stalls forever. Cursor and ledger counters
                # survive the rollback like hiwater; request records
                # injected into the discarded segment are lost external
                # input (they were real uplinks — the rollback cannot
                # un-receive them), so arr_injected keeps counting them
                # while arr_done never will. Load benchmarks run
                # Recovery.NONE; this path is exercised for exactness
                # only.
                arr_t=state.arr_t, arr_k=state.arr_k,
                arr_injected=state.arr_injected,
                arr_dropped=state.arr_dropped, arr_done=state.arr_done,
                soj_lo=state.soj_lo, soj_hi=state.soj_hi)

        def apply_supervision(state):
            # victims re-push records whose thief just died. Clearing uses
            # the raw repush mask (dead victims forget too); the actual
            # pushes additionally require the victim to be alive.
            repush = (state.sup_thief >= 0) & dying_now[jnp.clip(state.sup_thief, 0, W - 1)]
            pushing = repush & (state.alive & ~dying_now)[:, None]
            # compact each victim's repushed records to the front, slot order
            slot_order = jnp.argsort(~pushing, axis=1, stable=True)
            recs = jnp.take_along_axis(state.sup_buf, slot_order[:, :, None],
                                       axis=1)                    # (W, S, T)
            n_re = jnp.sum(pushing, axis=1).astype(jnp.int32)
            ovf = state.overflow + ses.push_many(recs, n_re)
            sup_thief = jnp.where(repush, -1, state.sup_thief)
            # dead worker's own state is lost
            ses.clear(dying_now)
            acc = jnp.where(dying_now, 0, state.acc)
            return state._replace(acc=acc, sup_thief=sup_thief,
                                  alive=alive, overflow=ovf,
                                  work=jnp.where(dying_now, 0, state.work),
                                  phase=jnp.where(dying_now, 0, state.phase),
                                  got=jnp.where(dying_now, False, state.got))

        if cfg.recovery == Recovery.TC:
            state = apply_tc(state, snap)
        elif cfg.recovery == Recovery.SUPERVISION:
            state = apply_supervision(state)
        else:
            ses.clear(dying_now)
            state = state._replace(alive=alive,
                                   acc=jnp.where(dying_now, 0, state.acc),
                                   work=jnp.where(dying_now, 0, state.work),
                                   phase=jnp.where(dying_now, 0, state.phase),
                                   got=jnp.where(dying_now, False, state.got))
        alive = state.alive

        # ------------- eclipse exits: wake-ups (elastic grow) ------------- #
        # A dead worker whose wake tick arrives rejoins as a fresh citizen:
        # empty deque (transplanted/lost at death — every recovery path
        # leaves dead deques empty), zero fail count, cleared supervision
        # ledger, no in-flight state. It resumes stealing this very tick
        # and is immediately stealable once it holds work.
        waking = (~alive) & _fires_now(wake_time, fail_period, t)
        alive = alive | waking
        state = state._replace(
            alive=alive,
            phase=jnp.where(waking, PHASE_RUN, state.phase),
            timer=jnp.where(waking, 0, state.timer),
            victim=jnp.where(waking, -1, state.victim),
            work=jnp.where(waking, 0, state.work),
            fails=jnp.where(waking, 0, state.fails),
            got=jnp.where(waking, False, state.got),
            sup_thief=jnp.where(waking[:, None], -1, state.sup_thief),
            sup_n=jnp.where(waking, 0, state.sup_n))

        # ------------- periodic checkpoint (TC) ---------------------------- #
        take_ckpt = ((p.ckpt_interval > 0)
                     & (t % jnp.maximum(p.ckpt_interval, 1) == 0))
        if cfg.recovery == Recovery.TC:
            # only TC consumes snapshots — other modes don't carry one. The
            # snapshot cut must see the post-recovery deque, so a fresh
            # session carries the rest of the tick.
            deq_mid = ses.finish()
            ses = _session(deq_mid)
            state = state._replace(deque=deq_mid)
            snap = jax.tree.map(lambda s, c: jnp.where(take_ckpt, c, s), snap, state)
        state = state._replace(
            ckpt_count=state.ckpt_count + take_ckpt.astype(jnp.int32))

        # ------------- open-loop arrival injection ------------------------- #
        # (core/arrivals.py) Candidate arr_k fires when the carried
        # next-candidate tick reaches t. Acceptance / station / gaps are
        # pure functions of (seed, arr_k).
        # Placed after the TC snapshot cut (a checkpoint never captures
        # half-injected state) and before PHASE_RUN, so an idle station can
        # pop the fresh request in the same tick.
        if ar is not None:
            a_fire = t == state.arr_t
            a_station = arrivals.station_of(ar, aseed, state.arr_k)
            a_accept = a_fire & arrivals.accepted(ar, aseed, state.arr_k, t)
            # a dead station drops the uplink on the floor — counted in
            # arr_dropped (and NOT pushed: work on a dead deque would leak
            # into the liveness sum and the run could never drain)
            a_live = a_accept & alive[a_station]
            a_batch = jnp.clip(p.arrival_batch, 1, arrivals.ARRIVAL_K)
            a_lanes = jnp.arange(arrivals.ARRIVAL_K, dtype=jnp.int32)
            # task_id = arr_k·ARRIVAL_K + lane, wrapped into non-negative
            # int32 (uniqueness wraps after 2^31 records — far beyond any
            # max_ticks horizon at one candidate per tick)
            a_ids = ((state.arr_k.astype(jnp.uint32)
                      * jnp.uint32(arrivals.ARRIVAL_K)
                      + a_lanes.astype(jnp.uint32))
                     & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
            a_recs = jnp.stack(
                [jnp.full((arrivals.ARRIVAL_K,), tasks.KIND_REQ, jnp.int32),
                 jnp.broadcast_to(ar.task_cost,
                                  (arrivals.ARRIVAL_K,)).astype(jnp.int32),
                 jnp.full((arrivals.ARRIVAL_K,), t, jnp.int32),
                 a_ids], axis=1)
            a_blk = jnp.zeros((W, arrivals.ARRIVAL_K, T),
                              jnp.int32).at[a_station].set(a_recs)
            a_counts = jnp.zeros((W,), jnp.int32).at[a_station].set(
                jnp.where(a_live, a_batch, 0))
            a_over = ses.push_many(a_blk, a_counts)
            a_lost = (jnp.sum(a_over)
                      + jnp.where(a_accept & ~alive[a_station], a_batch, 0))
            # advance the cursor past the fired candidate
            nxt = arrivals.gap_ticks(aseed, state.arr_k + 1,
                                     p.arrival_gap_q8)
            state = state._replace(
                arr_t=jnp.where(a_fire, jnp.minimum(t + nxt, _NEVER),
                                state.arr_t),
                arr_k=state.arr_k + a_fire.astype(jnp.int32),
                arr_injected=state.arr_injected + jnp.sum(a_counts - a_over),
                arr_dropped=state.arr_dropped + a_lost,
                overflow=state.overflow + a_over)

        # ------------- phase RUN: work / expand / start steal -------------- #
        active_tick = alive & (t % sp == 0)  # stragglers advance slowly
        running = (state.phase == PHASE_RUN) & active_tick
        burning = running & (state.work > 0)
        work = state.work - burning.astype(jnp.int32)

        can_expand = running & (~burning) & (ses.size > 0)
        task, popped = ses.pop(can_expand)
        ex = tasks.expand(task, popped, tables)
        over = ses.push_many(ex["children"], ex["n_children"])
        acc = (state.acc + ex["value"]) % tasks.RESULT_MOD
        work = work + jnp.maximum(ex["cost"] - 1, 0) * popped.astype(jnp.int32)
        nodes = state.nodes + ex["nodes"]
        busy = state.busy + (burning | popped).astype(jnp.int32)
        overflow = state.overflow + over.astype(jnp.int32)

        # open-loop sojourn ledger: a popped KIND_REQ record completes its
        # queueing phase here — price queue wait + nominal service in one
        # shot (the burn-down that follows is exactly ex["cost"] ticks of
        # work, so completion needs no extra carried state). Same-tick
        # inject-and-pop with cost c yields sojourn c, the floor.
        if ar is not None:
            is_req = popped & (task[:, 0] == tasks.KIND_REQ)
            soj = jnp.where(is_req, t - task[:, 2] + ex["cost"], 0)
            # 62-bit accumulation: the per-tick (W,)-sum must fit int32 —
            # at most one pop per worker per tick, each sojourn < 2^30, so
            # this binds only at W·sojourn ≥ 2^31 within ONE tick, far
            # beyond any configuration the suite or benches run
            s_lo = state.soj_lo + jnp.sum(soj)
            state = state._replace(
                arr_done=state.arr_done + jnp.sum(is_req.astype(jnp.int32)),
                soj_hi=state.soj_hi + (s_lo >> _HOP_LANE_BITS),
                soj_lo=s_lo & _HOP_LANE_MASK)

        # idle workers become thieves: request departs now, arrives in h·τ
        idle = running & (~burning) & (~popped) & (ses.size == 0)
        # retired workers (warned of shutdown) must not pull work back in
        idle = idle & ~_retired_mask(cfg, p.warn_ticks, fail_time,
                                     fail_period, t, W)
        fails_sel = state.fails  # fails row the draw (and its gate) sees
        victim_new = _select(code, escalate_after, tbl, key, idle, fails_sel,
                             W, link)
        has_victim = victim_new >= 0
        reach = None
        if ls is not None:
            # route-around: a victim with no live route (other component)
            # is unreachable — the flight never departs, no attempt is
            # counted, and the thief redraws at its next active tick.
            reach = lstate.same_component(ls, eidx, jnp.arange(W), victim_new)
            has_victim = has_victim & reach
        vhops = jnp.where(has_victim,
                          _hop_dist(mesh, tbl["coords"], victim_new), 0)
        if ls is None:
            req_ticks = vhops * p.hop_ticks
        else:
            # flight latency sampled from the departure epoch's link state
            req_ticks = jnp.where(has_victim, lstate.flight_ticks(
                ls, eidx, jnp.arange(W), victim_new,
                mesh.rows, mesh.cols, torus_full), 0)
        start_req = idle & has_victim & alive
        phase = jnp.where(start_req, PHASE_REQ, state.phase)
        timer = jnp.where(start_req, req_ticks, state.timer)
        victim = jnp.where(start_req, victim_new, state.victim)
        attempts = state.attempts + start_req.astype(jnp.int32)
        hop_units = jnp.sum(jnp.where(start_req, vhops, 0))

        # ------------- phase REQ: in flight / arrival ----------------------- #
        in_req = (phase == PHASE_REQ) & alive
        timer = jnp.where(in_req, jnp.maximum(timer - 1, 0), timer)
        arriving = in_req & (timer == 0)
        # victims must be alive to grant (dead satellites drop requests)
        valid_victim = arriving & alive[jnp.clip(victim, 0, W - 1)]
        if ls is not None:
            # deny the grant when an epoch flip mid-request severed the
            # reply path (different live-link component at arrival): loot
            # must never be launched into a partition. The empty-handed
            # reply below then prices as the nominal-RTT timeout.
            valid_victim = valid_victim & lstate.same_component(
                ls, eidx, victim, jnp.arange(W))
        plan = stealing.resolve_grants(jnp.where(valid_victim, victim, -1),
                                       ses.size, p.max_grants_per_victim)
        v = jnp.clip(plan.victim, 0, W - 1)
        stolen_blk = ses.export(plan.taken, stealing.GRANT_WIDTH)
        stolen = stolen_blk[v, jnp.clip(plan.rank, 0, stealing.GRANT_WIDTH - 1)]
        got = plan.got
        # victim-side steal ledger (who was stolen from, counted at grant)
        stolen_from = state.stolen_from + plan.taken
        # supervision: victims log (record, thief)
        if cfg.recovery == Recovery.SUPERVISION:
            sup_buf, sup_thief, sup_n = state.sup_buf, state.sup_thief, state.sup_n
            # scatter: for each granted thief w, write into victim's buffer;
            # ungranted lanes route to a padding row, not a no-op write
            vslot = jnp.clip(sup_n[v] + plan.rank, 0, S - 1)
            dst_v = jnp.where(got, v, W)
            sup_buf = jnp.concatenate(
                [sup_buf, jnp.zeros((1, S, T), jnp.int32)],
                axis=0).at[dst_v, vslot].set(stolen)[:W]
            sup_thief = jnp.concatenate(
                [sup_thief, jnp.full((1, S), -1, jnp.int32)],
                axis=0).at[dst_v, vslot].set(jnp.arange(W))[:W]
            sup_n = sup_n + jnp.zeros((W,), jnp.int32).at[v].add(got.astype(jnp.int32))
            state = state._replace(sup_buf=sup_buf, sup_thief=sup_thief,
                                   sup_n=jnp.minimum(sup_n, S - 1))
        # response departs: travel back
        resp_start = arriving
        phase = jnp.where(resp_start, PHASE_RESP, phase)
        back_hops = jnp.where(resp_start,
                              _hop_dist(mesh, tbl["coords"], victim), 0)
        if ls is None:
            back_ticks = back_hops * p.hop_ticks
        else:
            # reply priced on the victim→thief path at the *arrival* epoch
            # (which may differ from the request's departure epoch)
            back_ticks = jnp.where(resp_start, lstate.flight_ticks(
                ls, eidx, victim, jnp.arange(W),
                mesh.rows, mesh.cols, torus_full), 0)
        timer = jnp.where(resp_start, back_ticks, timer)
        hop_units = hop_units + jnp.sum(jnp.where(resp_start, back_hops, 0))
        loot = jnp.where(resp_start[:, None], stolen, state.loot)
        got_flight = jnp.where(resp_start, got, state.got)

        # exact 62-bit hop accumulation (int32 lanes with explicit carry)
        lo = state.hops_lo + hop_units.astype(jnp.int32)
        hops_hi = state.hops_hi + (lo >> _HOP_LANE_BITS)
        hops_lo = lo & _HOP_LANE_MASK

        # ------------- phase RESP: in flight / delivery --------------------- #
        in_resp = (phase == PHASE_RESP) & alive
        timer = jnp.where(in_resp, jnp.maximum(timer - 1, 0), timer)
        delivered = in_resp & (timer == 0)
        # thief-side import: a loot delivery landing on a full deque (filled
        # by a transplant/re-push while the steal was in flight) is a REAL
        # task loss — count it, don't swallow it
        want_import = delivered & got_flight
        imported = ses.push(loot, want_import)
        overflow = overflow + (want_import & ~imported).astype(jnp.int32)
        successes = state.successes + (delivered & got_flight).astype(jnp.int32)
        fails = jnp.where(delivered & got_flight, 0,
                          state.fails + (delivered & ~got_flight).astype(jnp.int32))
        phase = jnp.where(delivered, PHASE_RUN, phase)
        steal_wait = state.steal_wait + (in_req | in_resp).astype(jnp.int32)

        # every deque mutation of this tick is already committed
        deque_ = ses.finish()

        # ------------- flight recorder: canonical per-tick emission -------- #
        # Fixed order: DEATH, WAKE, EPOCH, NO_LIVE_VICTIM, attempt
        # resolutions, OVERFLOW, FAMINE transitions — then the tick's
        # time-series deltas against the entry state.
        if trc is not None:
            warr = jnp.arange(W)
            ep_lane = eidx if ls is not None else jnp.int32(0)
            tr = tracing.emit(tr, trc, dying_now, tick=t,
                              kind=tracing.EV_DEATH, worker=warr, victim=-1,
                              epoch=ep_lane)
            tr = tracing.emit(tr, trc, waking, tick=t, kind=tracing.EV_WAKE,
                              worker=warr, victim=-1, epoch=ep_lane)
            if ls is not None:
                tr = tracing.emit1(
                    tr, trc, (t > 0) & jnp.any(ls.epoch_starts == t),
                    tick=t, kind=tracing.EV_EPOCH, epoch=ep_lane)
                # a comp-gated draw never departs — but emit only for
                # workers that COULD attempt under this epoch's link state:
                # a fully victimless worker re-draws every tick without
                # an event (`_can_attempt`, with the same fails row the
                # draw itself saw)
                can_try = _can_attempt(code, escalate_after, tbl, ls, eidx,
                                       fails_sel, W)
                no_live = idle & (victim_new >= 0) & ~reach & can_try
                tr = tracing.emit(
                    tr, trc, no_live, tick=t, kind=tracing.EV_NO_LIVE_VICTIM,
                    worker=warr, victim=victim_new,
                    hops=_hop_dist(mesh, tbl["coords"],
                                   jnp.clip(victim_new, 0, W - 1)),
                    epoch=ep_lane)
            # open-loop ledger events: one ARRIVAL per record actually
            # injected (task_id in the hops lane), one SOJOURN per
            # completed request (inject tick in the victim lane, task_id
            # in hops, priced sojourn in rtt)
            if ar is not None:
                a_ok = a_lanes < (a_counts[a_station] - a_over[a_station])
                tr = tracing.emit(tr, trc, a_ok, tick=t,
                                  kind=tracing.EV_ARRIVAL, worker=a_station,
                                  victim=-1, hops=a_ids, epoch=ep_lane)
                tr = tracing.emit(tr, trc, is_req, tick=t,
                                  kind=tracing.EV_SOJOURN, worker=warr,
                                  victim=task[:, 2], hops=task[:, 3],
                                  rtt=soj, epoch=ep_lane)
            # attempt resolution at request arrival: the request leg was
            # banked in the (W,) req_ticks lane at departure, so the rtt
            # lane prices the full round trip (incl. route-around detours)
            req_lane = jnp.where(start_req, req_ticks, tr.req_ticks)
            tr = tr._replace(req_ticks=req_lane)
            kind_arr = jnp.where(
                ~valid_victim, tracing.EV_SEVERED_DENIAL,
                jnp.where(got, tracing.EV_GRANTED, tracing.EV_EMPTY_VICTIM))
            tr = tracing.emit(tr, trc, arriving, tick=t, kind=kind_arr,
                              worker=warr, victim=victim, hops=back_hops,
                              rtt=req_lane + back_ticks, epoch=ep_lane)
            # net per-tick overflow increase (a TC rollback can rewind the
            # counter — the trace keeps the discarded timeline, so only
            # fresh drops re-emit)
            ovf_delta = overflow - st_in.overflow
            tr = tracing.emit(tr, trc, ovf_delta > 0, tick=t,
                              kind=tracing.EV_OVERFLOW, worker=warr,
                              victim=-1, rtt=jnp.maximum(ovf_delta, 0),
                              epoch=ep_lane)
            # famine flag: end-of-tick total stealable supply == 0. Sizes
            # only change at deque-op ticks — always tick_fn-executed in
            # both modes — so the flag provably cannot toggle at skipped or
            # replayed ticks.
            famine_now = jnp.sum(deque_.size) == 0
            tr = tracing.emit1(tr, trc, famine_now & ~tr.famine, tick=t,
                               kind=tracing.EV_FAMINE_ENTER, epoch=ep_lane)
            tr = tracing.emit1(tr, trc, ~famine_now & tr.famine, tick=t,
                               kind=tracing.EV_FAMINE_EXIT, epoch=ep_lane)
            tr = tr._replace(famine=famine_now)
            tr = tracing.ts_add(
                tr, trc, t,
                busy=jnp.sum(busy) - jnp.sum(st_in.busy),
                queue=jnp.sum(deque_.size),
                inflight=jnp.sum(steal_wait) - jnp.sum(st_in.steal_wait),
                attempts=jnp.sum(attempts) - jnp.sum(st_in.attempts),
                successes=jnp.sum(successes) - jnp.sum(st_in.successes),
                alive=jnp.sum(alive.astype(jnp.int32)))

        new_state = state._replace(
            deque=deque_, acc=acc, work=work, fails=fails, phase=phase,
            timer=timer, victim=victim, loot=loot, got=got_flight & ~delivered,
            alive=alive, attempts=attempts, successes=successes, nodes=nodes,
            busy=busy, steal_wait=steal_wait, hops_lo=hops_lo, hops_hi=hops_hi,
            overflow=overflow, stolen_from=stolen_from,
            hiwater=jnp.maximum(state.hiwater, deque_.size))
        live = (jnp.sum(deque_.size) + jnp.sum(work)
                + jnp.sum((got_flight & ~delivered).astype(jnp.int32))) > 0
        if ar is not None:
            # open system: a transiently drained constellation stays live
            # while the candidate stream has a pending fire tick
            live = live | (state.arr_t < _NEVER)
        return new_state, snap, tr, t + 1, live

    def cond(carry):
        state, snap, tr, t, live, iters = carry
        return live & (t < cfg.max_ticks)

    def body(carry):
        state, snap, tr, t, _, iters = carry
        state, snap, tr, t, live = tick_fn((state, snap, tr, t))
        return state, snap, tr, t, live, iters + 1

    # non-TC modes don't carry the (W, C, T) snapshot copy through the loop
    snap0 = state0 if cfg.recovery == Recovery.TC else ()
    state, _, tr, ticks, _, iters = jax.lax.while_loop(
        cond, body, (state0, snap0, tr0, jnp.int32(0), jnp.bool_(True),
                     jnp.int32(0)))
    if trc is not None:
        # attempts still in their request flight when the run ended. The
        # rtt lane carries the banked
        # request leg — the outcome is unknown by construction.
        pend = (state.phase == PHASE_REQ) & state.alive
        ep_end = (lstate.epoch_index(ls.epoch_starts, ticks)
                  if ls is not None else jnp.int32(0))
        tr = tracing.emit(
            tr, trc, pend, tick=ticks, kind=tracing.EV_PENDING,
            worker=jnp.arange(W), victim=state.victim,
            hops=_hop_dist(mesh, tbl["coords"],
                           jnp.clip(state.victim, 0, W - 1)),
            rtt=tr.req_ticks, epoch=ep_end)
    return state, tr, ticks, iters


_sim_jit = partial(jax.jit, static_argnames=("workload", "mesh", "cfg"))(_sim_core)


def _check_cfg(cfg: SimConfig):
    if cfg.max_ticks >= int(_NEVER):
        raise ValueError(f"max_ticks must stay below {int(_NEVER)}")
    _check_params(cfg.params)
    if cfg.trace is not None:
        cfg.trace.validate()


def _check_params(p: SimParams):
    """Host-side validation of one (unstacked) `SimParams` point — the
    checks that used to live as trace-time asserts before these fields
    became traced values."""
    if int(p.max_grants_per_victim) > stealing.GRANT_WIDTH:
        raise ValueError(
            "max_grants_per_victim must be <= stealing.GRANT_WIDTH "
            f"({stealing.GRANT_WIDTH}), got {int(p.max_grants_per_victim)}")
    if not 0 <= int(p.strategy) < len(stealing.CODE_STRATEGIES):
        raise ValueError(f"unknown strategy code {int(p.strategy)}")
    if int(p.hop_ticks) < 0:
        raise ValueError("hop_ticks must be >= 0")
    if not 0 <= int(p.arrival_gap_q8) < (1 << 31):
        raise ValueError(
            "arrival_gap_q8 must be a non-negative int32 (mean gap ticks "
            f"x 256; 0 = closed system), got {int(p.arrival_gap_q8)}")
    if not 1 <= int(p.arrival_batch) <= arrivals.ARRIVAL_K:
        raise ValueError(
            f"arrival_batch must be in [1, {arrivals.ARRIVAL_K}], "
            f"got {int(p.arrival_batch)}")


def _ckpt_state_bytes(mesh: topo.MeshTopology, cfg: StaticConfig) -> int:
    return mesh.num_workers * cfg.capacity * 4 * 4 + mesh.num_workers * 4


def _finalize(state, tr, ticks, iters, mesh: topo.MeshTopology,
              cfg: StaticConfig) -> SimResult:
    att, suc = int(state.attempts.sum()), int(state.successes.sum())
    busy = int(np.asarray(state.busy, np.int64).sum())
    t = int(ticks)
    alive_n = int(state.alive.sum())
    hop_units = (int(state.hops_hi) << _HOP_LANE_BITS) + int(state.hops_lo)
    soj_sum = (int(state.soj_hi) << _HOP_LANE_BITS) + int(state.soj_lo)
    req_done = int(state.arr_done)
    trace = timeseries = None
    if cfg.trace is not None:
        trace, timeseries = tracing.finalize(tr, cfg.trace)
    return SimResult(
        result=int(np.asarray(state.acc, np.int64).sum() % int(tasks.RESULT_MOD)),
        ticks=t, nodes=int(state.nodes.sum()), attempts=att, successes=suc,
        p_success=suc / max(att, 1), busy_ticks=busy,
        steal_wait_ticks=int(np.asarray(state.steal_wait, np.int64).sum()),
        bytes_hops=float(hop_units * STEAL_MSG_BYTES),
        ckpt_bytes=float(int(state.ckpt_count) * _ckpt_state_bytes(mesh, cfg)),
        overflow=int(np.asarray(state.overflow, np.int64).sum()),
        utilization=busy / max(t * max(alive_n, 1), 1),
        per_worker_busy=np.asarray(state.busy),
        events=int(iters),
        per_worker_overflow=np.asarray(state.overflow),
        per_worker_stolen=np.asarray(state.stolen_from),
        per_worker_hiwater=np.asarray(state.hiwater),
        per_worker_attempts=np.asarray(state.attempts),
        per_worker_successes=np.asarray(state.successes),
        trace=trace, timeseries=timeseries,
        arrivals_injected=int(state.arr_injected),
        arrivals_dropped=int(state.arr_dropped),
        requests_done=req_done,
        sojourn_sum_ticks=soj_sum,
        sojourn_mean=soj_sum / max(req_done, 1),
        sojourn=tracing.sojourn_stats(trace) if trace is not None else None)


def _fail_speed_arrays(W, fail_time, speed, wake_time=None, fail_period=None):
    ft_np = np.asarray(fail_time if fail_time is not None
                       else -np.ones(W, np.int32), np.int32)
    wt_np = np.asarray(wake_time if wake_time is not None
                       else -np.ones(W, np.int32), np.int32)
    fp_np = np.asarray(fail_period if fail_period is not None
                       else -np.ones(W, np.int32), np.int32)
    bad = (wt_np >= 0) & ((ft_np < 0) | (wt_np <= ft_np))
    if bad.any():
        raise ValueError(
            "wake_time must be strictly after the worker's fail_time (and "
            f"only set for workers that fail); offending workers: "
            f"{np.where(bad)[0].tolist()}")
    per = fp_np != -1
    bad_p = per & (fp_np <= 0)
    # int32 fire arithmetic (`_next_fire`) needs period < 2**29; a worker
    # must die and wake exactly once per cycle, so the wake offset has to
    # land strictly inside it
    bad_p |= per & (fp_np >= (1 << 29))
    bad_p |= per & ((ft_np < 0) | (wt_np < 0) | (wt_np - ft_np >= fp_np))
    if bad_p.any():
        raise ValueError(
            "fail_period must be -1 (one-shot) or a positive cycle length "
            "< 2**29 with fail_time >= 0 and fail_time < wake_time < "
            f"fail_time + fail_period; offending workers: "
            f"{np.where(bad_p)[0].tolist()}")
    ft = jnp.asarray(ft_np)
    wt = jnp.asarray(wt_np)
    fp = jnp.asarray(fp_np)
    sp = jnp.asarray(speed if speed is not None
                     else np.ones(W, np.int32), jnp.int32)
    return ft, wt, fp, sp


def _linkstate_tables(linkstate, mesh, speed, routing="auto"):
    if linkstate is None:
        return None
    if speed is not None:
        raise ValueError(
            "pass straggler speeds through the LinkStateSchedule's per-epoch "
            "`speed` field, not the static `speed` argument, when simulating "
            "under a link-state schedule")
    if isinstance(linkstate, lstate.LinkStateArrays):
        # prebuilt device tables (e.g. a benchmark that wants the build
        # stats, or a sweep reusing one build) pass through as-is
        return linkstate
    return lstate.device_tables(linkstate, mesh, routing=routing)


def _check_arrivals(arr, params):
    """`arrival_gap_q8 > 0` (stream on) needs an `ArrivalConfig`; a config
    with the stream off is legal (tables built, zero candidates fire)."""
    if int(params.arrival_gap_q8) > 0 and arr is None:
        raise ValueError(
            "cfg.arrival_gap_q8 > 0 turns the open-loop request stream on; "
            "pass arrivals=ArrivalConfig(...) to describe it")
    if arr is not None and isinstance(arr, arrivals.ArrivalConfig):
        arr.validate()


def _arrival_tables(arr, mesh):
    if arr is None:
        return None
    if isinstance(arr, arrivals.ArrivalArrays):
        return arr  # prebuilt tables (a sweep reusing one build)
    return arrivals.device_tables(arr, mesh)


def simulate(workload, mesh: topo.MeshTopology, cfg: SimConfig | None = None,
             fail_time: np.ndarray | None = None,
             speed: np.ndarray | None = None,
             linkstate=None,
             wake_time: np.ndarray | None = None,
             fail_period: np.ndarray | None = None,
             routing_backend: str = "auto",
             arrivals=None) -> SimResult:
    """Run the tick simulator. `fail_time[w]` = death tick (-1: immortal);
    `wake_time[w]` = rejoin tick of a dead worker (-1: death is permanent;
    must be > fail_time[w] — eclipse exits wake with a fresh empty state);
    `fail_period[w]` = cycle length of a periodic (fail, wake) schedule
    (-1: one-shot): the worker dies at `fail + k*period` and wakes at
    `wake + k*period` every orbit, with the wake strictly inside the cycle;
    `speed[w]` = straggler divisor (1 = nominal). With `linkstate` (a
    `LinkStateSchedule`, or prebuilt `LinkStateArrays` accepted verbatim),
    hop latency / link availability / speeds follow the piecewise-constant
    schedule instead of the scalar `cfg.hop_ticks` (which is then unused);
    `routing_backend` picks the outage-table layout ('dense', 'sparse', or
    'auto' — sparse at W >= linkstate.SPARSE_AUTO_MIN_WORKERS). With
    `arrivals` (an `ArrivalConfig`, or prebuilt `ArrivalArrays` accepted
    verbatim) and `cfg.arrival_gap_q8 > 0`, an open-loop request stream
    feeds the root workload: tasks of `arrivals.task_cost` land on ground-
    station workers at i.i.d. exponential gaps (mean `arrival_gap_q8/256`
    ticks, thinned by the per-epoch rate schedule and on/off bursts), and
    `SimResult` reports their sojourn percentiles."""
    cfg = cfg or SimConfig()
    _check_cfg(cfg)
    scfg, params = cfg.split()
    _check_arrivals(arrivals, params)
    ls = _linkstate_tables(linkstate, mesh, speed, routing_backend)
    ar = _arrival_tables(arrivals, mesh)
    ft, wt, fp, sp = _fail_speed_arrays(mesh.num_workers, fail_time, speed,
                                        wake_time, fail_period)
    state, tr, ticks, iters = _sim_jit(workload, mesh, scfg, params, ft, wt,
                                       fp, sp, ls, ar)
    state, tr = jax.device_get((state, tr))
    return _finalize(state, tr, ticks, iters, mesh, scfg)


def simulate_sweep(workload, mesh: topo.MeshTopology, cfg: SimConfig,
                   params_list, devices=None, **kwargs) -> list[SimResult]:
    """A grid of points, one `simulate` call each, in order: every point
    (a `SimConfig` sharing `cfg`'s static half) is stepped on its own, one
    tick per loop iteration, on JAX's default device; `devices` is taken
    for the program's signature and not used. `kwargs` are `simulate`'s
    schedules and tables, shared by every point."""
    out = []
    for p in params_list:
        if p.static != cfg.static:
            raise ValueError("a sweep's points share one static config")
        out.append(simulate(workload, mesh, p, **kwargs))
    return out
