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

Event-leaping execution (``step_mode="leap"``, the default)
-----------------------------------------------------------
The model above is defined tick-by-tick, but almost all ticks are *dead*:
every worker is either burning down a multi-tick leaf, or waiting out a
steal-message flight, and the only state change is a uniform decrement.
The leap stepper exploits this. Each `lax.while_loop` iteration

  1. executes ONE tick with full semantics (expansion, grant resolution,
     failures, checkpoints — exactly the code the one-tick oracle runs,
     keyed by ``fold_in(key0, t)`` so randomness is a pure function of the
     tick index, not of how we reached it); then
  2. computes ``Δ = min`` over every pending event horizon — remaining
     `work` on running workers (straggler-aware), in-flight steal `timer`s,
     each worker's scheduled failure and pre-shed warning tick, and the
     next checkpoint tick — and advances the clock by Δ in one fused step,
     accumulating the per-tick stats (`busy` for burners, `steal_wait` for
     in-flight thieves) in bulk.

Iterations therefore scale with the number of *events* (task expansions,
steal phase transitions, failures, checkpoints), not the number of ticks:
with `hop_ticks` ≥ 1 or leaf costs > 1 the dead ticks collapse and
constellation-scale sweeps (W ≥ 640) become tractable.

Famine-window fast path (probe-cycle leaping)
---------------------------------------------
The leap above is throttled in the *famine-churn* regime (NEIGHBOR at
small W): idle workers re-probe empty neighbors every ~2τ (§3.1's
immediate retry), so nearly every tick carries a probe event and the leap
factor degenerates to ~1. But victim emptiness is deterministic between
true events — the lifeline-graph insight — so those retries carry no
information. `_famine_horizon` computes the first tick at which any deque
size can change: the next expansion of a task-holding worker, the next
request arrival at a currently-nonempty victim, the next granted-loot
delivery, the next probe opportunity of any thief whose drawable victim
set could reach a nonempty deque (`stealing.probe_may_succeed`, including
thieves currently mid-flight and ADAPTIVE escalation reachable within the
window), the flight transition of any mid-flight worker whose own deque
was refilled behind its back (supervision re-push / transplant — it will
pop right after delivering), and the recovery / checkpoint / epoch
horizons. Within that
window deque sizes are provably frozen, so by induction every steal
attempt fails deterministically; `famine_ff` then replays up to
``famine_batch`` such ticks per loop iteration in a bounded
`lax.while_loop` — only the probe phase machine, burn-downs, and stats,
no deque ops, no grant sort, no recovery machinery. The loop stops at the
window's end, or when the run goes idle, so it executes exactly the ticks
it replays; under vmap it runs to the longest lane's window, never past
``famine_batch``. Each step draws its tick's victims with
`stealing.victim_draws_code`, the exact per-tick ``fold_in(key0, t)``
sequence, so the result stays bit-identical.
Measured effect (bench_sim_throughput, NEIGHBOR W=100): leap factor ~1× →
~7× at τ=5 and ~14× at τ=1. Note the leap factor depends on the
famine-churn vs backlog regime, not just granularity: GLOBAL's thieves
idle in long multi-hop flights (plain leaping already wins), while
NEIGHBOR's saturate every tick with retries (the famine path is what
collapses them).

Equivalence guarantee: because the event tick runs the unmodified one-tick
code, the leap skips only ticks in which that code provably reduces to
the bulk decrement, and the famine batch replays only ticks whose steal
attempts provably fail (with the identical key schedule),
``step_mode="leap"`` produces `SimResult`s identical to
``step_mode="tick"`` (the seed one-tick stepper, kept as the test oracle) —
same `result`, `ticks`, `nodes`, `attempts`, `successes`, and per-worker
`busy`/`steal_wait`. The test suite asserts this over a matrix of
strategy × recovery × {pre-shed, straggler} configs, plus dedicated
famine-regime configs (small W, τ ∈ {1, 5}, mid-famine epoch flip and
failure) and a property sweep over `famine_batch` sizes.

Steal-conflict resolution uses sort-based segment ranking
(`stealing.segment_prefix`) and the victim-side export runs through
`deque.export_bottom` — optionally the Pallas `steal_compact` kernel
(``use_steal_kernel``; auto-enabled on TPU) — so the per-tick path never
materializes a (W, W) intermediate and W ≥ 2500 meshes fit comfortably.

Staged deque-ops backend (``deque_backend="staged"``; auto on TPU)
------------------------------------------------------------------
The event tick chains several deque mutations — expansion pop + children
push, grant export, loot import, and (under recovery) transplants /
re-pushes — each committing its own ``(W, C, T)`` buffer update. The
staged backend threads every one of those mutations through a
`deque.DequeOps` delta instead: virtual bottom/size cursors plus a
bounded per-worker push log, committed in ONE fused pass at the end of
the tick (`deque.apply`; the Pallas ``deque_apply`` kernel replays the
log with the rings resident in VMEM). Mid-tick reads (the popped record,
the exported bottom window, transplant source rings) are overlay-aware,
so the staged op sequence is bit-identical to the sequential one, which
survives as ``deque_backend="loop"`` — the conformance oracle, asserted
across the strategy × recovery × modifier matrix in both step modes. On
the common no-recovery path the push log is ``EXPAND_K + 1`` lanes.

Measured reality on CPU (a CPU container, W=4096, NEIGHBOR, τ=5): XLA CPU
already performs the per-op scatters *in place* inside the while_loop —
the "~8 sequential (W, C, T) scatters" never materialize as full-buffer
traffic — so the staged log's second write makes "staged" ~1.7x slower
per event than "loop" there, and the auto default keeps CPU on "loop".
What actually unlocked the W=4096 sweep was sizing `capacity` from
`SimResult.per_worker_hiwater` (occupancy peaks at ~10 tasks/worker on
the paper workload — 2048-slot rings were 200x oversized) plus the
PR 1–3 leap machinery; the staged backend is the TPU-facing data layout,
where per-element scatters don't vectorize and the one VMEM-resident
kernel commit per tick is the right shape. On a TPU v5e the staged
backend with both kernels reproduces the loop backend's results
(`chip_smoke.py`); its speed there against "loop" is not measured yet.

One compile, whole grid (static/traced `SimConfig` split)
---------------------------------------------------------
`SimConfig` is the user-facing knob set, but it is NOT the jit cache key.
`cfg.split()` separates it into a `StaticConfig` — the fields that change
program *structure* (capacity, step mode, famine batch, deque/routing
backends, recovery, supervision slots, trace shape) — and a `SimParams`
pytree of int32 leaves for everything that is just *data* to the compiled
graph: the strategy (a `lax.switch` code over `stealing.*_CODE` branch
tables), `hop_ticks` τ, escalation threshold, grant cap, warn/ckpt
scalars, and the PRNG seed. Sweeping any `SimParams` axis therefore
costs ZERO retraces: `simulate_batch` vmaps stacked params through one
compilation, and `simulate_sweep` runs a whole factorial grid
(strategy × τ × seed × …) in ONE compiled call — vmapped on a single
device, `shard_map`-sharded over a 1D "grid" device axis when several
are visible (points padded to a device multiple, trimmed on return).
Results are bit-identical to per-point `simulate()` calls (vmap's
while_loop batching freezes finished points), and `trace_count()` lets
tests pin the one-trace invariant. `benchmarks/sweep.py` builds the
crossover study on top.

Beyond the paper's model, the simulator also covers the SEC failure modes the
paper lists in §2.1/§5, each as an orthogonal, testable mechanism:

  * **failures** — a schedule kills workers at given ticks (radiation, power
    loss). Recovery options:
      - ``Recovery.TC``: coordinated task-level checkpointing every
        `ckpt_interval` ticks; on failure the constellation rolls back to the
        last snapshot and the dead worker's snapshot deque + accumulator are
        transplanted to its nearest live mesh neighbor. Exactly-once always —
        asserted in tests for arbitrary schedules.
      - ``Recovery.SUPERVISION``: every victim remembers the tasks stolen
        from it (ring buffer of `supervision_slots`); when a thief dies its
        victims re-push the un-acknowledged records, and the dead worker's
        local state is lost. Exact when nothing was re-stolen from the dead
        worker before its death (single-level protocol, per Kestor et al.
        [26]); the general nested case needs subtree acks — documented
        limitation, measured rather than hidden (see tests).
      - ``Recovery.NONE``: lost work stays lost (baseline for overhead).
  * **malleability** (§5/§6) — predictable shutdowns (battery/eclipse) give a
    `warn_ticks` lead; the doomed worker *pre-sheds*, pushing its entire
    deque and accumulator to live neighbors before sleeping. Exactly-once.
  * **stragglers** — per-worker `speed` divisors (a speed-s worker advances
    work only every s-th tick), modelling degraded satellites.
  * **time-varying link state** — pass a `linkstate.LinkStateSchedule` to
    `simulate`: per-epoch per-link τ (inter-plane oscillation), link up/down
    intervals (eclipse outages, cross-seam handovers) masking radius-1
    victim sets, and per-epoch straggler speeds. Flights are priced by
    dimension-order path sums at the departure epoch; `_next_event` gains a
    next-link-state-change horizon so leaps never cross an epoch boundary,
    preserving leap ≡ tick bit-exactness under dynamic schedules.
  * **route-around** — in epochs where a link is down, flights are priced
    along the epoch's live-link shortest-path detours (precompiled
    `linkstate` tables) instead of pretending the dimension-order path is
    still up. Fully-partitioned victims become *unreachable*: the thief
    never launches the flight (no attempt is counted — its routing layer
    already knows), escalated ADAPTIVE draws exclude other components, and
    a grant whose reply path was severed by an epoch flip mid-request is
    denied while the thief waits out the nominal RTT as a timeout — so no
    loot is ever launched into a partition and exactness is preserved.
  * **open-loop traffic** (`core/arrivals.py`) — pass `arrivals=` plus a
    nonzero `SimConfig.arrival_gap_q8`: ground stations continuously
    inject user requests (Poisson / bursty candidate streams with
    deterministic thinning, Zipf-skewed station hot spots, per-epoch
    rate schedules riding the link-state epoch machinery) as
    `tasks.KIND_REQ` leaf records. The next-candidate tick is carried in
    `SimState` and joins the leap horizons — and clips certified famine
    windows, since an injection un-freezes deque sizes — so leap ≡ tick
    bit-exactness extends to open systems. Per-request sojourns (queue
    wait + nominal service) accumulate exactly into
    `SimResult.sojourn_sum_ticks` / `requests_done`, and with tracing on
    every arrival/completion lands in the event ring, yielding
    p50/p90/p99/p999 sojourn percentiles (`SimResult.sojourn`) — the
    tail-latency SLO axis of the load–latency study
    (`benchmarks/load_latency.py`). The offered load itself
    (`arrival_gap_q8`, `arrival_batch`) is traced `SimParams` data: a
    load sweep costs zero retraces.
  * **wake-ups** (elastic grow) — pass `wake_time`: a dead worker rejoins
    at its wake tick with a fresh, empty state (deque re-armed, fail count
    and supervision ledger cleared), modelling eclipse *exits*. The woken
    worker resumes stealing and is immediately stealable itself; pre-shed
    retirement ends at the wake tick. `_next_event` and the famine window
    gain next-wake horizon terms, so leap ≡ tick bit-exactness survives
    mid-horizon rejoins (asserted in the conformance matrix tests).

Congestion accounting: every steal message contributes payload_bytes × hops
to `bytes_hops`, the quantity behind the paper's §4.2 remark that multi-hop
steals "would further penalize the global strategy". Totals accumulate in an
exact 62-bit integer (a pair of int32 lanes with explicit carry — JAX's
default int64-disabled mode would silently truncate) so long runs never lose
congestion counts to float32 rounding.

Profiling: the loop names its layers for `jax.profiler`. Device ops carry
`jax.named_scope`s in their `op_name` metadata — `stepper.tick` (children
`tick.linkstate`, `tick.preshed`, `tick.arrivals`, `tick.grant`,
`tick.commit`, `tick.recorder`), `stepper.leap` and `stepper.famine`.
The entry points write host spans on the profiler's clock: `simulate` (or
`simulate_batch` / `simulate_sweep`) over `simulate.prepare`, `.dispatch`,
`.wait`, `.fetch` and `.finalize`. The root span's arguments carry the
loop's exact `StepCounts`, summed over the call's points. With the
profiler off the scopes are metadata and the spans record nothing.
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
    # execution: "leap" = event-leaping stepper (fast, default);
    # "tick" = the seed one-tick-per-iteration stepper (equivalence oracle)
    step_mode: str = "leap"
    # famine fast path (leap mode only): max ticks of deterministically
    # failing probe cycles collapsed into ONE loop iteration by a pruned
    # replay that stops at the window's end (0 disables; bit-identical
    # either way — the cap only trades loop iterations against replay
    # steps)
    famine_batch: int = 64
    # Pallas kernels: the victim-side grant export (`steal_compact`, both
    # deque backends) and the staged-ops commit (`deque_apply`, staged
    # backend); None = auto (compiled kernels on TPU, plain jnp elsewhere)
    use_steal_kernel: bool | None = None
    # deque mutation backend: "staged" records every per-tick deque
    # mutation in a `deque.DequeOps` delta — virtual bot/size cursors plus
    # a bounded push log — and commits them in ONE fused pass per tick
    # (the Pallas deque_apply kernel); "loop" is the seed
    # one-scatter-per-op path, kept as the staged backend's bit-exactness
    # oracle (see the backend conformance matrix in tests). None = auto:
    # staged on TPU (per-op scatters don't fuse there; the VMEM-resident
    # kernel commit does), loop on CPU — measured on a CPU container, XLA
    # CPU already performs the per-op scatters in place inside the
    # while_loop, so the staged log's second write costs ~1.7x (module
    # docstring, "measured reality" note)
    deque_backend: str | None = None
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
    # (asserted by the zero-overhead jaxpr test). A `tracing.TraceConfig`
    # turns on the in-loop event ring + binned time series; leap mode then
    # emits a trace elementwise identical to the tick oracle's (bin
    # boundaries join the leap horizons; the famine replay re-emits the
    # failed-attempt events of the ticks it collapses).
    trace: "tracing.TraceConfig | None" = None

    @property
    def static(self) -> "StaticConfig":
        """The static (shape/program-structure) half — the jit cache key."""
        return StaticConfig(
            capacity=self.capacity, max_ticks=self.max_ticks,
            step_mode=self.step_mode, famine_batch=self.famine_batch,
            use_steal_kernel=self.use_steal_kernel,
            deque_backend=self.deque_backend, recovery=self.recovery,
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
    step_mode: str = "leap"
    famine_batch: int = 64
    use_steal_kernel: bool | None = None
    deque_backend: str | None = None
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


def stack_params(params_list) -> SimParams:
    """Stack `SimParams` points into one (G,)-leaved `SimParams` pytree —
    the grid argument of `simulate_sweep` (and, with a leading seed axis,
    of `_sim_batch_jit`)."""
    params_list = list(params_list)
    if not params_list:
        raise ValueError("stack_params needs at least one SimParams point")
    return jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x, jnp.int32) for x in xs]),
        *params_list)


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


class StepCounts(NamedTuple):
    """How the loop spent its ticks, counted in the loop carry: every
    iteration runs one tick in `tick_fn`; a famine replay then runs
    `famine_ticks` more, and the leap skips `leap_ticks`. Exact, with
    iterations + leap_ticks + famine_ticks == ticks. For one point the
    replay loop executes exactly `famine_ticks` steps, at most
    `famine_batch` per replay. Under vmap (`simulate_batch`,
    `simulate_sweep`) the replay's `lax.cond` becomes a select and the
    points share one replay loop, which runs, on every iteration, to the
    longest window among the points whose window was certified: those
    executed steps are shared, and a point's own carry cannot see them."""
    iterations: jax.Array      # () int32 loop iterations (SimResult.events)
    leap_ticks: jax.Array      # () int32 ticks skipped by the leap
    famine_ticks: jax.Array    # () int32 ticks replayed by `famine_ff`
    famine_replays: jax.Array  # () int32 iterations whose famine window
                               # was certified


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
    # loop iterations executed (== ticks in "tick" mode; == event ticks in
    # "leap" mode — the leap factor is ticks / events)
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
    """Append plan shared by both deque backends: where every transplanted
    record lands on its heir, which records the heir's capacity rejects,
    and the per-worker size delta. Their agreement is load-bearing for the
    staged ≡ loop backend conformance, so there is exactly one spelling.

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
    This is the loop-backend applier; `_stage_transplant` commits the same
    plan into a staged `DequeOps` delta instead.
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


def _stage_transplant(ops: dq.DequeOps, acc, src_mask, heir, overflow):
    """Staged-backend transplant: same plan as `_transplant`, committed into
    the push log. The source window read is overlay-aware, so records
    staged earlier in the tick (the dying worker's banked in-flight loot)
    transplant exactly as the loop backend's buffer read would see them."""
    W, cap, T = ops.buf0.shape
    src_tasks = dq.stage_window(ops, cap)                   # (W, cap, T)
    # `added` is recomputed inside stage_place from the records actually
    # logged (identical under a correct lane budget; see stage_place)
    ranks, offset, write, dropped, _ = _transplant_plan(
        ops.size, src_mask, heir, cap)
    overflow = overflow.at[heir].add(jnp.where(src_mask, dropped, 0))
    ops = dq.stage_place(ops, jnp.broadcast_to(heir[:, None], (W, cap)),
                         offset[:, None] + ranks, src_tasks, write)
    ops = dq.stage_clear(ops, src_mask)
    return ops, _transplant_acc(acc, src_mask, heir), overflow


def _lane_budget(cfg: StaticConfig, arrivals_on: bool = False) -> int:
    """Static push-log width of the staged backend: an upper bound on the
    staged pushes any single worker can *accept* in one tick. Accepted
    pushes are bounded by free room plus slots freed mid-tick (one
    expansion pop + at most GRANT_WIDTH exported grants), so transplant
    appends can never exceed capacity + GRANT_WIDTH + 1 on top of the
    always-on expansion-children + loot-import lanes. Sized per config:
    the common (no-recovery) path stays at EXPAND_K + 1 lanes."""
    L = tasks.EXPAND_K + 1          # expansion children + thief-side loot import
    if arrivals_on:
        # open-loop injection: up to ARRIVAL_K request records land on one
        # station's deque in the same tick as its expansion push
        L += arrivals.ARRIVAL_K
    if cfg.recovery == Recovery.SUPERVISION:
        L += min(cfg.supervision_slots, cfg.capacity)
    if cfg.preshed or cfg.recovery == Recovery.TC:
        # pre-shed / rollback transplants plus the dying worker's loot bank
        L += cfg.capacity + stealing.GRANT_WIDTH + 2
    return L


class _LoopDeques:
    """Per-op deque backend (`deque_backend="loop"`): every mutation commits
    its own `(W, C, T)` buffer update — the seed semantics, kept as the
    staged backend's bit-exactness oracle."""

    def __init__(self, state: dq.DequeState, use_kernel: bool):
        self.st = state
        self.use_kernel = use_kernel

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
        stolen, self.st = dq.export_bottom(self.st, grants, width,
                                           use_kernel=self.use_kernel)
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


class _StagedDeques:
    """Staged deque backend (`deque_backend="staged"`): mutations accumulate
    in a `deque.DequeOps` delta — virtual cursors plus a bounded push log —
    and `finish()` commits the whole tick in ONE fused scatter (the Pallas
    `deque_apply` kernel when kernels are enabled). Mid-tick reads are
    overlay-aware, so the op sequence is bit-identical to `_LoopDeques`."""

    def __init__(self, state: dq.DequeState, lanes: int, use_kernel: bool):
        self.ops = dq.stage(state, lanes)
        self.use_kernel = use_kernel

    @property
    def size(self):
        return self.ops.size

    def push(self, task, mask):
        self.ops, ok = dq.stage_push(self.ops, task, mask)
        return ok

    def push_many(self, tasks_, counts):
        self.ops, over = dq.stage_push_many(self.ops, tasks_, counts)
        return over

    def pop(self, mask):
        self.ops, task, ok = dq.stage_pop(self.ops, mask)
        return task, ok

    def export(self, grants, width):
        self.ops, stolen = dq.stage_export(self.ops, grants, width,
                                           self.use_kernel)
        return stolen

    def clear(self, mask):
        self.ops = dq.stage_clear(self.ops, mask)

    def select(self, pred, other: dq.DequeState):
        self.ops = dq.stage_select(self.ops, pred, other)

    def transplant(self, acc, src_mask, heir, overflow):
        self.ops, acc, overflow = _stage_transplant(self.ops, acc, src_mask,
                                                    heir, overflow)
        return acc, overflow

    def finish(self) -> dq.DequeState:
        return dq.apply(self.ops, use_kernel=self.use_kernel)


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
    `_select` + the departure gate could produce a flight — the leap
    stepper skips idle workers for which this is False. The strategy is a
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


def _epoch_link_tables(tbl, ls, eidx):
    """Per-epoch victim-set tables under the active link state: the
    link_up-masked neighbor table, the reachability-masked radius-2 table,
    and the component row (None when the schedule has no outage epochs).
    Shared by `_famine_horizon` and the famine replay — their agreement is
    load-bearing for leap ≡ tick bit-identity, so there is exactly one
    spelling of these masks."""
    nbr_tab = jnp.where(ls.link_up[eidx] & (tbl["neighbors"] >= 0),
                        tbl["neighbors"], topo.NO_NEIGHBOR)
    r2_tab = _masked_radius2(tbl, ls, eidx)
    comp_row = ls.comp[eidx] if lstate.has_outage_tables(ls) else None
    return nbr_tab, r2_tab, comp_row


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
    every alive worker (the only consumers — dead workers never read it).
    Shared by the tick path, both horizons, and the famine replay so the
    predicate can never drift between them."""
    if not cfg.preshed:
        return jnp.zeros((W,), bool)
    nf = _next_fire(fail_time, fail_period, t)
    return (nf < _NEVER) & (t >= nf - warn_ticks)


def _scheduled_horizons(ne, t, alive, fail_time, wake_time, fail_period,
                        cfg: StaticConfig, p: SimParams, ls, arr_t=None):
    """Clip `ne` at every scheduled global event: deaths (and pre-shed
    warnings) of still-alive workers, wake-ups of dead ones, periodic
    checkpoints, and link-state epoch boundaries. Periodic (fail, wake)
    schedules clip at EVERY cycle's boundary via `_next_fire`, so leaps
    and famine windows land exactly on second-orbit eclipses too. Shared
    by `_next_event` and `_famine_horizon` so the two horizons can never
    drift apart on these correctness-critical terms.
    """
    nf = _next_fire(fail_time, fail_period, t)
    nw = _next_fire(wake_time, fail_period, t)
    ne = jnp.minimum(ne, jnp.min(jnp.where(alive, nf, _NEVER)))
    # eclipse exits: a dead worker with a pending wake rejoins mid-horizon
    ne = jnp.minimum(ne, jnp.min(jnp.where(~alive, nw, _NEVER)))
    if cfg.preshed:
        warn_at = nf - p.warn_ticks
        ne = jnp.minimum(ne, jnp.min(
            jnp.where(alive & (nf < _NEVER) & (warn_at >= t),
                      warn_at, _NEVER)))
    # ckpt_interval is a traced sweep axis: the term is always in the graph,
    # neutralized (`_NEVER`) when the interval is 0
    ck = jnp.maximum(p.ckpt_interval, 1)
    ne = jnp.minimum(ne, jnp.where(p.ckpt_interval > 0,
                                   t + ((ck - t % ck) % ck), _NEVER))
    # next link-state change: a leap or famine window must never jump across
    # an epoch boundary (τ, link availability, and speed all switch there)
    if ls is not None:
        ne = jnp.minimum(ne, lstate.next_change(ls.epoch_starts, t, _NEVER))
        if cfg.trace is not None:
            # the EPOCH ring event is stamped by tick_fn at the flip tick
            # itself, so under tracing a window may never *start* at an
            # epoch boundary the stepper didn't execute: clip inclusively
            # (>= t, vs next_change's strictly-after), matching the
            # inclusive `_next_fire` semantics deaths and wakes already
            # have. When t is a boundary this yields a delta-0 leap and the
            # next iteration runs tick_fn there — one extra loop iteration
            # per flip, traced runs only.
            ne = jnp.minimum(ne, jnp.min(jnp.where(
                ls.epoch_starts >= t, ls.epoch_starts, _NEVER)))
    # flight recorder: a window's bulk time-series contribution is scattered
    # into ONE bin, so windows may never straddle a bin boundary (static
    # branch — untraced runs compile without this term)
    if cfg.trace is not None:
        ne = jnp.minimum(ne, tracing.next_bin_boundary(cfg.trace, t, _NEVER))
    # open-loop arrivals: the next candidate tick is a first-class horizon.
    # A leap may never jump it (injection runs inside tick_fn), and a
    # certified famine window must END there — an injection un-freezes
    # deque sizes, voiding the every-probe-fails certificate (static
    # branch: closed runs compile without the term).
    if arr_t is not None:
        ne = jnp.minimum(ne, arr_t)
    return ne


def _next_event(state: SimState, t, speed, fail_time, wake_time, fail_period,
                cfg: StaticConfig, p: SimParams, W: int, tbl, ls, ar=None):
    """First tick >= t at which any worker does more than a bulk decrement.

    Conservative (may return a tick with no visible state change — that
    costs one loop iteration, never correctness): the leap stepper skips
    exactly the ticks in which `tick_fn` provably reduces to
    work/timer decrements plus busy/steal_wait accumulation.
    """
    alive = state.alive
    if ls is None:
        eidx, sp = None, speed
    else:
        eidx, sp = _epoch_view(ls, t)
    # first straggler-active tick >= t per worker
    t0 = t + ((sp - t % sp) % sp)
    run = (state.phase == PHASE_RUN) & alive
    # burning workers: event when work hits 0 on their work-th active tick
    burn_ev = t0 + state.work * sp
    # work-exhausted workers expand (deque nonempty) or start a steal (if a
    # victim is reachable under the current link state) at their next active
    # tick — unless retired by a pre-shed warning (they idle until death).
    retired = _retired_mask(cfg, p.warn_ticks, fail_time, fail_period, t, W)
    can_try = _can_attempt(p.strategy, p.escalate_after, tbl, ls, eidx,
                           state.fails, W)
    idle_acts = (state.deque.size > 0) | (can_try & ~retired)
    run_ev = jnp.where(state.work > 0, burn_ev,
                       jnp.where(idle_acts, t0, _NEVER))
    ev = jnp.where(run, run_ev, _NEVER)
    # in-flight steal messages arrive when the timer reaches 0
    flight = (state.phase != PHASE_RUN) & alive
    ev = jnp.where(flight, t + jnp.maximum(state.timer - 1, 0), ev)
    return _scheduled_horizons(jnp.min(ev), t, alive, fail_time, wake_time,
                               fail_period, cfg, p, ls,
                               state.arr_t if ar is not None else None)


def _famine_horizon(state: SimState, t, speed, fail_time, wake_time,
                    fail_period, cfg: StaticConfig, p: SimParams, W: int,
                    mesh: topo.MeshTopology, tbl, ls, ar=None):
    """First tick >= t at which any deque size can change (or a recovery /
    checkpoint / epoch event fires) — the famine-window horizon.

    Within ``[t, horizon)`` every deque size is provably frozen: no worker
    with a nonempty deque reaches an expansion tick, no steal request
    arrives at a currently-nonempty victim, no granted loot is delivered,
    and no thief whose drawable victim set could reach a nonempty deque
    (`stealing.probe_may_succeed`) starts a probe. By induction over the
    window, emptiness of every probed victim persists, so every steal
    attempt in the window fails deterministically and the whole stretch
    reduces to burn-downs, flight-timer decrements, and failing probe
    cycles — exactly what the famine batch replays. Unlike `_next_event`,
    probe starts / arrivals / deliveries of those provably-failing cycles
    are NOT events here.
    """
    alive = state.alive
    if ls is None:
        eidx, sp = None, speed
        nbr_tab = tbl["neighbors"]
        r2_tab, comp_row = tbl["radius2"], None
        # a probe cycle always costs >= 1 tick, even at hop_ticks=0
        min_cycle = jnp.maximum(2 * p.hop_ticks - 1, 1)
    else:
        eidx, sp = _epoch_view(ls, t)
        nbr_tab, r2_tab, comp_row = _epoch_link_tables(tbl, ls, eidx)
        min_cycle = jnp.maximum(2 * lstate.min_link_tau(ls, eidx) - 1, 1)
    nonempty = state.deque.size > 0
    t0 = t + ((sp - t % sp) % sp)
    run = (state.phase == PHASE_RUN) & alive
    burn_ev = t0 + state.work * sp
    retired = _retired_mask(cfg, p.warn_ticks, fail_time, fail_period, t, W)
    risky = stealing.probe_may_succeed_code(
        p.strategy, nonempty, state.fails, nbr_tab, r2_tab,
        escalate_after=p.escalate_after, window=cfg.famine_batch,
        min_cycle=min_cycle, num_workers=W, comp_row=comp_row)
    # holders expand when their burn ends; risky thieves (a drawable victim
    # may be nonempty) end the window at their next probe opportunity
    acts = nonempty | (risky & ~retired)
    run_ev = jnp.where(state.work > 0, burn_ev, t0)
    ev = jnp.where(run & acts, run_ev, _NEVER)
    # in-flight: a request arriving at a nonempty victim may be granted; a
    # response carrying granted loot delivers into a deque; and a flier
    # whose OWN deque is nonempty (a supervision re-push or transplant
    # landed on it mid-flight) will pop/expand right after its delivery —
    # the batched replay has no expansion path, so the window must end at
    # its flight transition
    v = jnp.clip(state.victim, 0, W - 1)
    flight_risky = (jnp.where(state.phase == PHASE_REQ, nonempty[v], state.got)
                    | nonempty)
    flight = (state.phase != PHASE_RUN) & alive
    flight_ev = jnp.where(flight_risky, t + jnp.maximum(state.timer - 1, 0),
                          _NEVER)
    # a RISKY worker currently mid-flight fails its present attempt, but its
    # NEXT draw comes from the full victim set and could hit a nonempty
    # deque — the window must end before that probe starts. REQ workers
    # deliver at arrival + (response flight − 1); RESP at timer expiry; the
    # probe follows at their first straggler-active tick after delivery.
    if ls is None:
        back = topo.hop_dist(mesh, tbl["coords"], v) * p.hop_ticks
    else:
        back = lstate.flight_ticks(ls, eidx, state.victim, jnp.arange(W),
                                   mesh.rows, mesh.cols, mesh.torus_full())
    arrive = t + jnp.maximum(state.timer - 1, 0)
    deliver = jnp.where(state.phase == PHASE_REQ,
                        arrive + jnp.maximum(back - 1, 0), arrive)
    d1 = deliver + 1
    next_probe = d1 + ((sp - d1 % sp) % sp)
    flight_ev = jnp.minimum(flight_ev, jnp.where(risky & ~retired,
                                                 next_probe, _NEVER))
    ev = jnp.where(flight, flight_ev, ev)
    return _scheduled_horizons(jnp.min(ev), t, alive, fail_time, wake_time,
                               fail_period, cfg, p, ls,
                               state.arr_t if ar is not None else None)


# Bumped once per jax TRACE of `_sim_core` (i.e. per jit cache miss of
# `_sim_jit` / `_sim_batch_jit` / the sharded sweep entry). Read via
# `trace_count()` — the compile-count regression tests and the sweep
# engine's single-compile assertion diff it around a grid run.
_TRACE_COUNT = 0


def trace_count() -> int:
    """Number of times `_sim_core` has been traced in this process."""
    return _TRACE_COUNT


def _sim_core(workload, mesh: topo.MeshTopology, cfg: StaticConfig,
              p: SimParams, fail_time, wake_time, fail_period, speed,
              ls=None, ar=None, points_axis=None):
    """The device loop of one point. `points_axis` names the vmap axis
    when the batched entry points run many points in one loop; the famine
    replay then runs one trip count for all of them (see `famine_ff`)."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    W = mesh.num_workers
    torus_full = mesh.torus_full()
    tbl = _mesh_tables(mesh)
    tables = workload.tables()
    S = cfg.supervision_slots
    code, escalate_after = p.strategy, p.escalate_after
    key0 = jax.random.PRNGKey(p.seed)
    use_kernel = (cfg.use_steal_kernel if cfg.use_steal_kernel is not None
                  else jax.default_backend() == "tpu")

    deques = dq.make(W, cfg.capacity)
    T = deques.buf.shape[2]  # task record width — single source of truth
    root = jnp.asarray(workload.root_task())
    assert root.shape[-1] == T, (
        f"root task width {root.shape[-1]} != deque record width {T}")
    deques, _ = dq.push_top(deques, jnp.broadcast_to(root[None], (W, T)),
                            jnp.arange(W) == 0)
    staged = (cfg.deque_backend == "staged"
              or (cfg.deque_backend is None
                  and jax.default_backend() == "tpu"))
    lanes_full = _lane_budget(cfg, ar is not None)

    def _session(deq, lanes):
        if staged:
            return _StagedDeques(deq, lanes, use_kernel)
        return _LoopDeques(deq, use_kernel)

    # open-loop arrival stream: the first candidate's fire tick. The whole
    # stream is a pure function of (seed, candidate index) — see
    # core/arrivals.py — so the carried cursor (arr_t, arr_k) is the ONLY
    # stream state, and the next fire tick doubles as a leap horizon.
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
            with jax.named_scope("tick.linkstate"):
                eidx, sp = _epoch_view(ls, t)
                link = (ls.link_up[eidx], ls.link_tau[eidx],
                        _masked_radius2(tbl, ls, eidx))

        # ------------- scheduled failures / shutdowns --------------------- #
        # periodic schedules fire at base + k·period (one-shot: base == t)
        dying_now = alive & _fires_now(fail_time, fail_period, t)
        warned = jnp.zeros((W,), bool)
        if cfg.preshed:
            with jax.named_scope("tick.preshed"):
                warned = alive & _fires_now(fail_time, fail_period,
                                            t + p.warn_ticks)

        # every deque mutation below goes through the session: the staged
        # backend accumulates them into one end-of-tick apply, the loop
        # backend commits op by op (the oracle). `state.deque` is stale
        # until ses.finish() lands in new_state.
        ses = _session(state.deque, lanes_full)

        # malleable pre-shed: migrate whole deque+acc one warn window early,
        # then a final flush at the (predictable) death tick catches any loot
        # delivered in between. Retired workers stop stealing (see below).
        acc, overflow = state.acc, state.overflow
        if cfg.preshed:
            with jax.named_scope("tick.preshed"):
                heir = _nearest_alive_neighbor(
                    tbl, alive & ~warned & ~dying_now, jnp.arange(W))
                acc, overflow = ses.transplant(acc, warned, heir, overflow)
                # death-tick flush: bank in-flight loot into own deque,
                # then move all
                flush = dying_now
                want_bank = flush & state.got
                banked = ses.push(state.loot, want_bank)
                overflow = overflow + (want_bank
                                       & ~banked).astype(jnp.int32)
                acc, overflow = ses.transplant(acc, flush, heir, overflow)
                state = state._replace(
                    got=jnp.where(flush, False, state.got))

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
            # the session owns the live deque: on rollback it discards
            # everything staged (incl. this tick's pre-shed moves) and
            # resets to the snapshot, mirroring the wholesale merge below
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
            # snapshot cut must see the post-recovery deque, so the staged
            # ops commit here and a fresh session (back at the common-path
            # lane budget) carries the rest of the tick — TC ticks pay two
            # fused applies instead of one.
            with jax.named_scope("tick.commit"):
                deq_mid = ses.finish()
            ses = _session(deq_mid, tasks.EXPAND_K + 1
                           + (arrivals.ARRIVAL_K if ar is not None else 0))
            state = state._replace(deque=deq_mid)
            snap = jax.tree.map(lambda s, c: jnp.where(take_ckpt, c, s), snap, state)
        state = state._replace(
            ckpt_count=state.ckpt_count + take_ckpt.astype(jnp.int32))

        # ------------- open-loop arrival injection ------------------------- #
        # (core/arrivals.py) Candidate arr_k fires when the carried
        # next-candidate tick reaches t. arr_t is a leap horizon, so both
        # step modes execute this tick through the identical code below;
        # acceptance / station / gaps are pure functions of (seed, arr_k),
        # never of how the stepper reached t — the leap ≡ tick invariant.
        # Placed after the TC snapshot cut (a checkpoint never captures
        # half-injected state) and before PHASE_RUN, so an idle station can
        # pop the fresh request in the same tick.
        if ar is not None:
            with jax.named_scope("tick.arrivals"):
                a_fire = t == state.arr_t
                a_station = arrivals.station_of(ar, aseed, state.arr_k)
                a_accept = a_fire & arrivals.accepted(ar, aseed,
                                                      state.arr_k, t)
                # a dead station drops the uplink on the floor — counted
                # in arr_dropped (and NOT pushed: work on a dead deque would
                # leak into the liveness sum and the run could never drain)
                a_live = a_accept & alive[a_station]
                a_batch = jnp.clip(p.arrival_batch, 1, arrivals.ARRIVAL_K)
                a_lanes = jnp.arange(arrivals.ARRIVAL_K, dtype=jnp.int32)
                # task_id = arr_k·ARRIVAL_K + lane, wrapped into
                # non-negative int32 (uniqueness wraps after 2^31 records —
                # far beyond any max_ticks horizon at one candidate per tick)
                a_ids = ((state.arr_k.astype(jnp.uint32)
                          * jnp.uint32(arrivals.ARRIVAL_K)
                          + a_lanes.astype(jnp.uint32))
                         & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
                a_recs = jnp.stack(
                    [jnp.full((arrivals.ARRIVAL_K,), tasks.KIND_REQ,
                              jnp.int32),
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
                          + jnp.where(a_accept & ~alive[a_station],
                                      a_batch, 0))
                # advance the cursor past the fired candidate — thinned and
                # dead-station candidates cost one horizon visit too
                # (conservative for the famine window, never wrong)
                nxt = arrivals.gap_ticks(aseed, state.arr_k + 1,
                                         p.arrival_gap_q8)
                state = state._replace(
                    arr_t=jnp.where(a_fire, jnp.minimum(t + nxt, _NEVER),
                                    state.arr_t),
                    arr_k=state.arr_k + a_fire.astype(jnp.int32),
                    arr_injected=(state.arr_injected
                                  + jnp.sum(a_counts - a_over)),
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
        with jax.named_scope("tick.grant"):
            plan = stealing.resolve_grants(jnp.where(valid_victim, victim, -1),
                                           ses.size, p.max_grants_per_victim)
            v = jnp.clip(plan.victim, 0, W - 1)
            stolen_blk = ses.export(plan.taken, stealing.GRANT_WIDTH)
            stolen = stolen_blk[v, jnp.clip(plan.rank, 0,
                                            stealing.GRANT_WIDTH - 1)]
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

        # the ONE fused commit of every staged deque mutation this tick
        # (loop backend: already-committed state, a no-op here)
        with jax.named_scope("tick.commit"):
            deque_ = ses.finish()

        # ------------- flight recorder: canonical per-tick emission -------- #
        # Fixed order (so leap-mode rings compare elementwise against the
        # oracle's): DEATH, WAKE, EPOCH, NO_LIVE_VICTIM, attempt
        # resolutions, OVERFLOW, FAMINE transitions — then the tick's
        # time-series deltas against the entry state.
        if trc is not None:
            with jax.named_scope("tick.recorder"):
                warr = jnp.arange(W)
                ep_lane = eidx if ls is not None else jnp.int32(0)
                tr = tracing.emit(tr, trc, dying_now, tick=t,
                                  kind=tracing.EV_DEATH, worker=warr,
                                  victim=-1, epoch=ep_lane)
                tr = tracing.emit(tr, trc, waking, tick=t,
                                  kind=tracing.EV_WAKE, worker=warr,
                                  victim=-1, epoch=ep_lane)
                if ls is not None:
                    tr = tracing.emit1(
                        tr, trc, (t > 0) & jnp.any(ls.epoch_starts == t),
                        tick=t, kind=tracing.EV_EPOCH, epoch=ep_lane)
                    # a comp-gated draw never departs — but emit only for
                    # workers that COULD attempt under this epoch's link
                    # state: a fully victimless worker re-draws every oracle
                    # tick, and those ticks are provably eventless (the leap
                    # skips them; `_can_attempt` is the shared predicate,
                    # with the same fails row the draw itself saw)
                    can_try = _can_attempt(code, escalate_after, tbl, ls,
                                           eidx, fails_sel, W)
                    no_live = idle & (victim_new >= 0) & ~reach & can_try
                    tr = tracing.emit(
                        tr, trc, no_live, tick=t,
                        kind=tracing.EV_NO_LIVE_VICTIM,
                        worker=warr, victim=victim_new,
                        hops=_hop_dist(mesh, tbl["coords"],
                                       jnp.clip(victim_new, 0, W - 1)),
                        epoch=ep_lane)
                # open-loop ledger events: one ARRIVAL per record actually
                # injected (task_id in the hops lane), one SOJOURN per
                # completed request (inject tick in the victim lane,
                # task_id in hops, priced sojourn in rtt) — both at deque-op
                # ticks, which tick_fn executes in both step modes, so ring
                # equality is inherited, not re-proven
                if ar is not None:
                    a_ok = a_lanes < (a_counts[a_station]
                                      - a_over[a_station])
                    tr = tracing.emit(tr, trc, a_ok, tick=t,
                                      kind=tracing.EV_ARRIVAL,
                                      worker=a_station, victim=-1,
                                      hops=a_ids, epoch=ep_lane)
                    tr = tracing.emit(tr, trc, is_req, tick=t,
                                      kind=tracing.EV_SOJOURN, worker=warr,
                                      victim=task[:, 2], hops=task[:, 3],
                                      rtt=soj, epoch=ep_lane)
                # attempt resolution at request arrival: the request leg
                # was banked in the (W,) req_ticks lane at departure, so the
                # rtt lane prices the full round trip (incl. route-around
                # detours)
                req_lane = jnp.where(start_req, req_ticks, tr.req_ticks)
                tr = tr._replace(req_ticks=req_lane)
                kind_arr = jnp.where(
                    ~valid_victim, tracing.EV_SEVERED_DENIAL,
                    jnp.where(got, tracing.EV_GRANTED,
                              tracing.EV_EMPTY_VICTIM))
                tr = tracing.emit(tr, trc, arriving, tick=t, kind=kind_arr,
                                  worker=warr, victim=victim, hops=back_hops,
                                  rtt=req_lane + back_ticks, epoch=ep_lane)
                # net per-tick overflow increase (a TC rollback can rewind
                # the counter — the trace keeps the discarded timeline, so
                # only fresh drops re-emit)
                ovf_delta = overflow - st_in.overflow
                tr = tracing.emit(tr, trc, ovf_delta > 0, tick=t,
                                  kind=tracing.EV_OVERFLOW, worker=warr,
                                  victim=-1, rtt=jnp.maximum(ovf_delta, 0),
                                  epoch=ep_lane)
                # famine flag: end-of-tick total stealable supply == 0.
                # Sizes only change at deque-op ticks — always
                # tick_fn-executed in both modes — so the flag provably
                # cannot toggle at skipped or replayed ticks.
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

    def leap(state: SimState, tr, t, live, ne):
        """Fused fast-forward over the dead ticks in [t, ne) — `ne` is the
        caller-supplied `_next_event` horizon for the current state.

        Returns (state, t, live). If the window's bulk burn consumes the
        LAST pending work, the one-tick stepper would have exited right
        after the final burn tick — land exactly there (not on the next
        event tick, which would run a phantom extra tick) and clear live.
        """
        # within [t, ne) the epoch is fixed (ne never exceeds the next
        # link-state change), so one speed row governs the whole window
        sp = speed if ls is None else _epoch_view(ls, t)[1]
        delta = jnp.clip(jnp.minimum(ne, cfg.max_ticks) - t, 0, None)
        delta = jnp.where(live, delta, 0)
        t0 = t + ((sp - t % sp) % sp)  # first active tick >= t
        burning = (state.phase == PHASE_RUN) & state.alive & (state.work > 0)
        # burners: one work unit per straggler-active tick in the window
        n_in = lambda d: ((t + d + sp - 1) // sp - (t + sp - 1) // sp)
        nact = jnp.where(burning, jnp.minimum(n_in(delta), state.work), 0)
        drained = (jnp.sum(state.deque.size) + jnp.sum(state.work - nact)
                   + jnp.sum(state.got.astype(jnp.int32))) == 0
        if ar is not None:
            # open system: never early-exit a transient drain while the
            # candidate stream is still pending (arr_t bounds ne anyway)
            drained = drained & (state.arr_t >= _NEVER)
        # tick right after the last burn of the burners that finish in-window
        exit_t = jnp.max(jnp.where(
            burning & (nact == state.work),
            t0 + (state.work - 1) * sp + 1, 0))
        delta = jnp.where(live & drained,
                          jnp.minimum(delta, jnp.maximum(exit_t - t, 0)),
                          delta)
        nact = jnp.where(burning, jnp.minimum(n_in(delta), state.work), 0)
        # in-flight messages: timers tick down, thieves accumulate wait
        flight = (state.phase != PHASE_RUN) & state.alive
        dflt = jnp.where(flight, delta, 0)
        if trc is not None:
            # bulk window contribution [t, t+delta): sizes and liveness are
            # frozen over a leap window, and `_scheduled_horizons` clipped
            # delta at the next bin boundary, so the whole window lands in
            # tick t's bin — identical to the oracle's per-tick adds
            tr = tracing.ts_add(
                tr, trc, t, busy=jnp.sum(nact),
                queue=jnp.sum(state.deque.size) * delta,
                inflight=jnp.sum(dflt), attempts=0, successes=0,
                alive=jnp.sum(state.alive.astype(jnp.int32)) * delta)
        return state._replace(
            timer=state.timer - dflt,
            steal_wait=state.steal_wait + dflt,
            work=state.work - nact,
            busy=state.busy + nact), tr, t + delta, live & ~drained

    FB = cfg.famine_batch
    famine_on = cfg.step_mode == "leap" and FB > 0

    def famine_ff(state: SimState, tr, t, live, ne_all):
        """Collapse up to FB ticks of deterministically failing probe cycles
        into this loop iteration (the famine-churn fast path).

        `_famine_horizon` certifies that every deque size is frozen over the
        window, so the replay below needs no deque ops, no grant
        resolution, and no recovery machinery — only the probe phase
        machine, burn-downs, and stats. It is a `lax.while_loop` over the
        window's ticks that stops at the window's end or when the run goes
        idle, so it executes exactly the ticks it replays. Under the batched
        entry points' vmap all points share one trip count, the longest
        window, and a point past its own window steps through masked. Each
        step draws its tick's victims with `stealing.victim_draws_code`, the
        exact ``fold_in(key0, t)``-keyed per-tick sequence, keeping the
        result bit-identical to the one-tick oracle. Returns
        (state, tr, t, live, ne, ran): `ne` is the `_next_event` horizon of
        the returned state, so the trailing leap never recomputes it, and
        `ran` says whether the replay ran.
        """
        ne_risky = _famine_horizon(state, t, speed, fail_time, wake_time,
                                   fail_period, cfg, p, W, mesh, tbl, ls, ar)
        hi = jnp.minimum(ne_risky, cfg.max_ticks)
        delta = jnp.clip(hi - t, 0, FB)
        # profitable only when probe-cycle events (counted by _next_event but
        # not by the famine horizon) actually occur inside the batch range;
        # otherwise the plain leap jumps the stretch for free. LIFELINE has
        # no probe churn to collapse — its thieves park on lifelines — so
        # the fast path is predicate-gated off for that strategy code.
        pred = (live & (delta > 0) & (ne_all < jnp.minimum(hi, t + FB))
                & (code != stealing.LIFELINE_CODE))

        def fast(state, tr, t, live):
            if ls is None:
                eidx0, sp0 = None, speed
                nbr_tab, adapt_tab = tbl["neighbors"], None
                r2_tab, comp0 = tbl["radius2"], None
            else:
                eidx0, sp0 = _epoch_view(ls, t)
                nbr_tab, r2_tab, comp0 = _epoch_link_tables(tbl, ls, eidx0)
                adapt_tab = stealing.cheapest_live_table(
                    nbr_tab, ls.link_tau[eidx0])
            empty0 = state.deque.size == 0
            alive0 = state.alive
            got0 = state.got
            ep0 = eidx0 if ls is not None else jnp.int32(0)
            frozen_supply = (jnp.sum(state.deque.size)
                             + jnp.sum(got0.astype(jnp.int32)))
            # open-system liveness inside the replay: the window ends at or
            # before arr_t (a `_scheduled_horizons` clip), so the flag is
            # frozen over the whole window
            open_live = (state.arr_t < _NEVER) if ar is not None else None
            warr = jnp.arange(W)
            t_end = t + delta

            def active(carry):
                # `pred` is True wherever `fast` is called alone; under vmap
                # the cond runs `fast` for every lane, and a lane whose
                # replay is discarded must not lengthen the loop
                t_c, live_c = carry[11:13]
                return (t_c < t_end) & live_c & pred

            def in_window(carry):
                act = active(carry)
                if points_axis is None:
                    return act
                # one trip count for all lanes, the longest window: a
                # per-lane predicate would make vmap select every carry,
                # the recorder ring included, on every step
                return jax.lax.psum(act.astype(jnp.int32), points_axis) > 0

            def step(carry):
                if trc is not None:
                    (phase, timer, victim, fails, work, loot, attempts, busy,
                     steal_wait, hops_lo, hops_hi, tj, live_c,
                     ev, n, req_lane) = carry
                else:
                    (phase, timer, victim, fails, work, loot, attempts, busy,
                     steal_wait, hops_lo, hops_hi, tj, live_c) = carry
                # False only for a lane past its own window under vmap
                act = active(carry)
                near_j, far_j = stealing.victim_draws_code(
                    code, key0, tj, nbr_tab, r2_tab, num_workers=W,
                    adaptive_table=adapt_tab)
                # ---- phase RUN: burn / start a (failing) probe ---------- #
                active_tick = alive0 & (tj % sp0 == 0)
                running = (phase == PHASE_RUN) & active_tick
                burning = running & (work > 0) & act
                work = work - burning.astype(jnp.int32)
                busy = busy + burning.astype(jnp.int32)
                idle = running & ~burning & empty0 & act
                idle = idle & ~_retired_mask(cfg, p.warn_ticks, fail_time,
                                             fail_period, tj, W)
                chosen = jnp.where(
                    (code == stealing.ADAPTIVE_CODE)
                    & (fails >= escalate_after), far_j, near_j)
                victim_new = jnp.where(idle, chosen, topo.NO_NEIGHBOR)
                start_req = idle & (victim_new >= 0)
                if comp0 is not None:
                    # mirror the tick path's departure gate: a draw in a
                    # different live-link component never launches (only
                    # GLOBAL can draw one — near/far tables are masked)
                    same_c = comp0[jnp.clip(victim_new, 0, W - 1)] == comp0
                    start_req = start_req & same_c
                    if trc is not None:
                        # re-emit the gated-draw events the collapsed ticks
                        # would have produced, under the identical
                        # attempt-capability gate the oracle applies (fails
                        # from the replay carry — deliveries inside the
                        # window do advance it)
                        no_live = (idle & (victim_new >= 0) & ~same_c
                                   & _can_attempt(code, escalate_after, tbl,
                                                  ls, eidx0, fails, W))
                        ev, n = tracing.emit_raw(
                            ev, n, trc.ring_capacity, no_live, tick=tj,
                            kind=tracing.EV_NO_LIVE_VICTIM, worker=warr,
                            victim=victim_new,
                            hops=_hop_dist(mesh, tbl["coords"],
                                           jnp.clip(victim_new, 0, W - 1)),
                            epoch=ep0)
                vhops = jnp.where(start_req,
                                  _hop_dist(mesh, tbl["coords"], victim_new), 0)
                if ls is None:
                    req_ticks = vhops * p.hop_ticks
                else:
                    req_ticks = jnp.where(start_req, lstate.flight_ticks(
                        ls, eidx0, warr, victim_new,
                        mesh.rows, mesh.cols, torus_full), 0)
                phase = jnp.where(start_req, PHASE_REQ, phase)
                timer = jnp.where(start_req, req_ticks, timer)
                victim = jnp.where(start_req, victim_new, victim)
                attempts = attempts + start_req.astype(jnp.int32)
                hop_units = jnp.sum(jnp.where(start_req, vhops, 0))
                if trc is not None:
                    # bank the request leg for the rtt lane, as the oracle
                    # tick does at departure
                    req_lane = jnp.where(start_req, req_ticks, req_lane)
                # ---- phase REQ: flight / arrival (grant always fails) --- #
                in_req = (phase == PHASE_REQ) & alive0 & act
                timer = jnp.where(in_req, jnp.maximum(timer - 1, 0), timer)
                resp_start = in_req & (timer == 0)
                back_hops = jnp.where(resp_start,
                                      _hop_dist(mesh, tbl["coords"], victim), 0)
                if ls is None:
                    back_ticks = back_hops * p.hop_ticks
                else:
                    back_ticks = jnp.where(resp_start, lstate.flight_ticks(
                        ls, eidx0, victim, warr,
                        mesh.rows, mesh.cols, torus_full), 0)
                if trc is not None:
                    # every arrival in a certified famine window fails; the
                    # oracle's classification needs only window-frozen state
                    # (alive + component rows): a dead or severed victim is
                    # the nominal-RTT timeout denial, a live reachable one
                    # the empty-victim miss. GRANTED is impossible here by
                    # the window certificate.
                    v_c = jnp.clip(victim, 0, W - 1)
                    valid0 = alive0[v_c]
                    if comp0 is not None:
                        valid0 = valid0 & (comp0[v_c] == comp0)
                    kind_a = jnp.where(valid0, tracing.EV_EMPTY_VICTIM,
                                       tracing.EV_SEVERED_DENIAL)
                    ev, n = tracing.emit_raw(
                        ev, n, trc.ring_capacity, resp_start, tick=tj,
                        kind=kind_a, worker=warr, victim=victim,
                        hops=back_hops, rtt=req_lane + back_ticks,
                        epoch=ep0)
                phase = jnp.where(resp_start, PHASE_RESP, phase)
                timer = jnp.where(resp_start, back_ticks, timer)
                hop_units = hop_units + jnp.sum(jnp.where(resp_start,
                                                          back_hops, 0))
                loot = jnp.where(resp_start[:, None], 0, loot)
                lo = hops_lo + hop_units.astype(jnp.int32)
                hops_hi = hops_hi + (lo >> _HOP_LANE_BITS)
                hops_lo = lo & _HOP_LANE_MASK
                # ---- phase RESP: flight / delivery (empty-handed) ------- #
                in_resp = (phase == PHASE_RESP) & alive0 & act
                timer = jnp.where(in_resp, jnp.maximum(timer - 1, 0), timer)
                delivered = in_resp & (timer == 0)
                fails = fails + (delivered & ~got0).astype(jnp.int32)
                phase = jnp.where(delivered, PHASE_RUN, phase)
                steal_wait = steal_wait + (in_req | in_resp).astype(jnp.int32)
                sup_live = (jnp.sum(work) + frozen_supply) > 0
                if ar is not None:
                    sup_live = sup_live | open_live
                out = (phase, timer, victim, fails, work, loot, attempts,
                       busy, steal_wait, hops_lo, hops_hi,
                       tj + act.astype(jnp.int32),
                       jnp.where(act, sup_live, live_c))
                if trc is not None:
                    out = out + (ev, n, req_lane)
                return out

            carry0 = (state.phase, state.timer, state.victim, state.fails,
                      state.work, state.loot, state.attempts, state.busy,
                      state.steal_wait, state.hops_lo, state.hops_hi, t, live)
            if trc is not None:
                carry0 = carry0 + (tr.ev, tr.n, tr.req_ticks)
            out = jax.lax.while_loop(in_window, step, carry0)
            (phase, timer, victim, fails, work, loot, attempts, busy,
             steal_wait, hops_lo, hops_hi, t_out, live_out) = out[:13]
            new_state = state._replace(
                phase=phase, timer=timer, victim=victim, fails=fails,
                work=work, loot=loot, attempts=attempts, busy=busy,
                steal_wait=steal_wait, hops_lo=hops_lo, hops_hi=hops_hi)
            if trc is not None:
                ev_out, n_out, req_out = out[13:]
                tr = tr._replace(ev=ev_out, n=n_out, req_ticks=req_out)
                # bulk time-series contribution of the replayed stretch —
                # sizes, liveness, and (by the window certificate)
                # successes are frozen, and `_famine_horizon` was clipped
                # at the next bin boundary, so the whole window lands in
                # tick t's bin
                executed = t_out - t
                tr = tracing.ts_add(
                    tr, trc, t,
                    busy=jnp.sum(busy) - jnp.sum(state.busy),
                    queue=jnp.sum(state.deque.size) * executed,
                    inflight=(jnp.sum(steal_wait)
                              - jnp.sum(state.steal_wait)),
                    attempts=jnp.sum(attempts) - jnp.sum(state.attempts),
                    successes=0,
                    alive=jnp.sum(alive0.astype(jnp.int32)) * executed)
            return new_state, tr, t_out, live_out, _next_event(
                new_state, t_out, speed, fail_time, wake_time, fail_period,
                cfg, p, W, tbl, ls, ar)

        state, tr, t, live, ne = jax.lax.cond(
            pred, fast, lambda s, r, tt, lv: (s, r, tt, lv, ne_all),
            state, tr, t, live)
        return state, tr, t, live, ne, pred

    def cond(carry):
        state, snap, tr, t, live, counts = carry
        return live & (t < cfg.max_ticks)

    def body(carry):
        state, snap, tr, t, _, counts = carry
        with jax.named_scope("stepper.tick"):
            state, snap, tr, t, live = tick_fn((state, snap, tr, t))
        leap_ticks = famine_ticks = replayed = jnp.int32(0)
        if cfg.step_mode == "leap":
            with jax.named_scope("stepper.leap"):
                ne = _next_event(state, t, speed, fail_time, wake_time,
                                 fail_period, cfg, p, W, tbl, ls, ar)
            if famine_on:
                t_in = t
                with jax.named_scope("stepper.famine"):
                    state, tr, t, live, ne, ran = famine_ff(state, tr, t,
                                                            live, ne)
                famine_ticks = t - t_in
                replayed = ran.astype(jnp.int32)
            t_in = t
            with jax.named_scope("stepper.leap"):
                state, tr, t, live = leap(state, tr, t, live, ne)
            leap_ticks = t - t_in
        counts = StepCounts(counts.iterations + 1,
                            counts.leap_ticks + leap_ticks,
                            counts.famine_ticks + famine_ticks,
                            counts.famine_replays + replayed)
        return state, snap, tr, t, live, counts

    # non-TC modes don't carry the (W, C, T) snapshot copy through the loop
    snap0 = state0 if cfg.recovery == Recovery.TC else ()
    zero = jnp.int32(0)
    state, _, tr, ticks, _, counts = jax.lax.while_loop(
        cond, body, (state0, snap0, tr0, zero, jnp.bool_(True),
                     StepCounts(zero, zero, zero, zero)))
    if trc is not None:
        # attempts still in their request flight when the run ended: both
        # step modes reach the identical final state, so the flush (and its
        # ring slots) is identical too. The rtt lane carries the banked
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
    return state, tr, ticks, counts


_sim_jit = partial(jax.jit, static_argnames=("workload", "mesh", "cfg"))(_sim_core)

# vmap axis name of the points of `simulate_batch` and `simulate_sweep`
_POINTS = "points"


@partial(jax.jit, static_argnames=("workload", "mesh", "cfg"))
def _sim_batch_jit(workload, mesh, cfg, params, fail_time, wake_time,
                   fail_period, speed, ls, ar):
    """vmap of `_sim_core` over a (B,)-stacked `SimParams` pytree (plus
    per-point schedules). `cfg` is the static half only — every grid of
    params points with the same `StaticConfig` reuses ONE compilation, and
    `simulate_batch` / the single-device `simulate_sweep` path share this
    cache entry. `ls` / `ar` (link-state and arrival tables) are shared
    across the batch, closed over un-vmapped."""
    return jax.vmap(
        lambda p, ft, wt, fp, sp: _sim_core(workload, mesh, cfg, p, ft, wt,
                                            fp, sp, ls, ar, _POINTS),
        axis_name=_POINTS)(params, fail_time, wake_time, fail_period, speed)


# (workload, mesh, StaticConfig, devices) -> jitted shard_map'd sweep fn.
# jax.jit would key on these statics anyway; the dict just skips rebuilding
# the shard_map wrapper object so repeated sweeps hit the XLA cache.
_SWEEP_SHARD_CACHE: dict = {}


def _sharded_sweep_fn(workload, mesh, cfg: StaticConfig, devs):
    key = (workload, mesh, cfg, devs)
    fn = _SWEEP_SHARD_CACHE.get(key)
    if fn is None:
        from jax.sharding import Mesh as DeviceMesh
        from jax.sharding import PartitionSpec as P
        dmesh = DeviceMesh(np.array(devs), ("grid",))

        def shard_body(params, ft, wt, fp, sp, ls, ar):
            # per-device slice of the grid; vmap the points inside the shard
            return jax.vmap(
                lambda p, a, b, c, d: _sim_core(workload, mesh, cfg, p, a,
                                                b, c, d, ls, ar, _POINTS),
                axis_name=_POINTS)(params, ft, wt, fp, sp)

        fn = jax.jit(jax.shard_map(
            shard_body, mesh=dmesh,
            in_specs=(P("grid"),) * 5 + (P(), P()),  # ls + ar replicated
            out_specs=P("grid"), check_vma=False))
        _SWEEP_SHARD_CACHE[key] = fn
    return fn


def _check_cfg(cfg: SimConfig):
    if cfg.step_mode not in ("leap", "tick"):
        raise ValueError(f"step_mode must be 'leap' or 'tick', got {cfg.step_mode!r}")
    if cfg.deque_backend not in (None, "staged", "loop"):
        raise ValueError(
            "deque_backend must be 'staged', 'loop', or None (auto), "
            f"got {cfg.deque_backend!r}")
    if cfg.max_ticks >= int(_NEVER):
        raise ValueError(f"max_ticks must stay below {int(_NEVER)}")
    if cfg.famine_batch < 0:
        raise ValueError("famine_batch must be >= 0 (0 disables the fast path)")
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


def _finalize(state, tr, ticks, counts: StepCounts,
              mesh: topo.MeshTopology, cfg: StaticConfig) -> SimResult:
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
        events=int(counts.iterations),
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


_span = jax.profiler.TraceAnnotation


def _run_loop(root, dispatch, finalize, n_points: int, **args):
    """The entry points' shared tail, one span each under the open `root`
    span: dispatch the compiled loop (a cache lookup, or a retrace and
    compile), wait for the device, fetch the outputs, and `finalize` the
    first `n_points` of them. While the profiler records, `root` gets
    `args` and the step counts summed over those points as its
    arguments."""
    with _span("simulate.dispatch"):
        out = dispatch()
    with _span("simulate.wait"):
        out = jax.block_until_ready(out)
    with _span("simulate.fetch"):
        state, tr, ticks, counts = jax.device_get(out)
    with _span("simulate.finalize"):
        results = finalize(state, tr, ticks, counts)
    if _span.is_enabled():
        def total(x):
            return int(np.asarray(x, np.int64).reshape(-1)[:n_points].sum())
        root.set_metadata(ticks=total(ticks), **{
            k: total(v) for k, v in counts._asdict().items()}, **args)
    return results


def _point_results(mesh, scfg, points: int):
    """`finalize` of a batched call: one `SimResult` per point."""
    def finalize(states, trs, ticks, counts):
        return [
            _finalize(jax.tree.map(lambda x: x[i], states),
                      jax.tree.map(lambda x: x[i], trs), ticks[i],
                      jax.tree.map(lambda x: x[i], counts), mesh, scfg)
            for i in range(points)]
    return finalize


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


def _prepare(mesh, cfg, fail_time=None, speed=None, linkstate=None,
             wake_time=None, fail_period=None, routing_backend="auto",
             arrivals=None):
    """`simulate`'s checks and the loop's arguments after the workload and
    mesh: the static config, the traced params, and the device arrays."""
    cfg = cfg or SimConfig()
    _check_cfg(cfg)
    scfg, params = cfg.split()
    _check_arrivals(arrivals, params)
    ls = _linkstate_tables(linkstate, mesh, speed, routing_backend)
    ar = _arrival_tables(arrivals, mesh)
    ft, wt, fp, sp = _fail_speed_arrays(
        mesh.num_workers, fail_time, speed, wake_time, fail_period)
    return scfg, params, (ft, wt, fp, sp, ls, ar)


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
    with _span("simulate") as root:
        with _span("simulate.prepare"):
            scfg, params, arrays = _prepare(
                mesh, cfg, fail_time, speed, linkstate, wake_time,
                fail_period, routing_backend, arrivals)
        return _run_loop(
            root,
            lambda: _sim_jit(workload, mesh, scfg, params, *arrays),
            lambda *out: _finalize(*out, mesh, scfg), 1,
            seed=int(params.seed), workers=mesh.num_workers)


def lower_simulate(workload, mesh: topo.MeshTopology,
                   cfg: SimConfig | None = None, **kwargs):
    """The `jax.stages.Lowered` loop that `simulate(workload, mesh, cfg,
    **kwargs)` runs. Its `.compile().as_text()` is the program the device
    runs, each op with the `op_name` path of its scopes: what a profile's
    op names mean in the loop's layers."""
    scfg, params, arrays = _prepare(mesh, cfg, **kwargs)
    return _sim_jit.lower(workload, mesh, scfg, params, *arrays)


def simulate_batch(workload, mesh: topo.MeshTopology,
                   cfg: SimConfig | None = None,
                   seeds=(0,),
                   fail_time: np.ndarray | None = None,
                   speed: np.ndarray | None = None,
                   linkstate=None,
                   wake_time: np.ndarray | None = None,
                   fail_period: np.ndarray | None = None,
                   routing_backend: str = "auto",
                   arrivals=None) -> list[SimResult]:
    """Run one simulation per seed in a single compiled, vmapped call.

    All seeds share `cfg` (whose own `seed` field is ignored), the failure
    and wake-up schedules, the straggler speeds, and the link-state
    schedule; the batch advances until the slowest seed terminates. Returns
    one `SimResult` per seed, identical to
    `simulate(..., cfg._replace-ish(seed=s))` run serially.
    """
    with _span("simulate_batch") as root:
        with _span("simulate.prepare"):
            cfg = cfg or SimConfig()
            _check_cfg(cfg)
            scfg, params = cfg.split()
            _check_arrivals(arrivals, params)
            ls = _linkstate_tables(linkstate, mesh, speed, routing_backend)
            ar = _arrival_tables(arrivals, mesh)
            W = mesh.num_workers
            seeds = list(seeds)
            pstack = stack_params([params._replace(seed=int(s))
                                   for s in seeds])
            ft, wt, fp, sp = _fail_speed_arrays(W, fail_time, speed,
                                                wake_time, fail_period)
            B = len(seeds)
            fts = jnp.broadcast_to(ft[None], (B, W))
            wts = jnp.broadcast_to(wt[None], (B, W))
            fps = jnp.broadcast_to(fp[None], (B, W))
            sps = jnp.broadcast_to(sp[None], (B, W))
        return _run_loop(
            root,
            lambda: _sim_batch_jit(workload, mesh, scfg, pstack, fts, wts,
                                   fps, sps, ls, ar),
            _point_results(mesh, scfg, B), B, points=B, workers=W)


def simulate_sweep(workload, mesh: topo.MeshTopology, cfg,
                   params_list,
                   fail_time: np.ndarray | None = None,
                   speed: np.ndarray | None = None,
                   linkstate=None,
                   wake_time: np.ndarray | None = None,
                   fail_period: np.ndarray | None = None,
                   routing_backend: str = "auto",
                   devices=None,
                   arrivals=None) -> list[SimResult]:
    """Run a whole grid of `SimParams` points in ONE compiled call.

    `cfg` supplies the static half (a `StaticConfig`, or a `SimConfig`
    whose traced fields are ignored); `params_list` is the grid — a
    sequence of `SimParams` (or `SimConfig`s, split on the fly). Every
    point shares the workload, mesh, failure/wake schedules, straggler
    speeds, and link-state schedule; sweep those by calling again (they
    are shapes/schedules, not scalar axes).

    On one local device the grid is vmapped through the same jit cache
    entry `simulate_batch` uses; on multiple devices it is sharded across
    them with `shard_map` over a 1D "grid" device axis (the grid is padded
    to a device multiple by repeating the last point, trimmed on return).
    Either way the whole grid costs ONE `_sim_core` trace per distinct
    `StaticConfig` (pinned by `trace_count()` tests), and results are
    bit-identical to per-point `simulate()` calls — vmap's while_loop
    batching freezes finished points while the rest run on.

    Returns one `SimResult` per point, in `params_list` order.
    """
    scfg = cfg.static if isinstance(cfg, SimConfig) else cfg
    pts = [p.params if isinstance(p, SimConfig) else p for p in params_list]
    if not pts:
        return []
    with _span("simulate_sweep") as root:
        with _span("simulate.prepare"):
            for p in pts:
                _check_params(p)
            if scfg.trace is not None:
                scfg.trace.validate()
            G = len(pts)
            W = mesh.num_workers
            ls = _linkstate_tables(linkstate, mesh, speed, routing_backend)
            for p in pts:
                _check_arrivals(arrivals, p)
            ar = _arrival_tables(arrivals, mesh)
            ft, wt, fp, sp = _fail_speed_arrays(W, fail_time, speed,
                                                wake_time, fail_period)
            devs = (tuple(devices) if devices is not None
                    else tuple(jax.local_devices()))
            sharded = len(devs) > 1
            if sharded:  # pad the grid to a device multiple (trimmed below)
                pts = pts + [pts[-1]] * ((-G) % len(devs))
            pstack = stack_params(pts)
            B = len(pts)
            fts = jnp.broadcast_to(ft[None], (B, W))
            wts = jnp.broadcast_to(wt[None], (B, W))
            fps = jnp.broadcast_to(fp[None], (B, W))
            sps = jnp.broadcast_to(sp[None], (B, W))
            fn = (_sharded_sweep_fn(workload, mesh, scfg, devs) if sharded
                  else partial(_sim_batch_jit, workload, mesh, scfg))
        return _run_loop(
            root, lambda: fn(pstack, fts, wts, fps, sps, ls, ar),
            _point_results(mesh, scfg, G), G, points=G, workers=W)
