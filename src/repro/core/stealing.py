"""Victim-selection strategies and steal-conflict resolution (paper §3.1).

The paper's two strategies:

  * GLOBAL   — victim uniform at random over *all other* workers (the HPC
               default; on a mesh this is a multi-hop exchange).
  * NEIGHBOR — victim uniform at random over the thief's directly connected
               mesh neighbors only; every steal is single-hop, no fallback.

Beyond-paper strategies (motivated by §5 Related Work and §6 Future Work):

  * LIFELINE — a fixed preferred-target set (hypercube lifelines, Saraswat et
               al.) tried first, falling back to global random (retains the
               multi-hop fallback the paper removes — useful as a contrast).
  * ADAPTIVE — the paper's future-work idea: start neighbor-only, and after
               `escalate_after` consecutive failed attempts widen the victim
               set to radius-2 mesh neighbors (still cheap: ≤2 hops).

All selection functions are pure, vectorized over workers, and usable inside
`lax.while_loop`. Conflict resolution (`resolve_grants`) is shared by every
strategy: when several thieves pick the same victim in one steal round, they
are ranked deterministically and the victim grants one bottom task per thief
while tasks (and the per-round grant budget) last — the bulk-synchronous
analogue of the victim serializing steal responses.
"""

from __future__ import annotations

import enum
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import topology as topo


class Strategy(enum.Enum):
    GLOBAL = "global"
    NEIGHBOR = "neighbor"
    LIFELINE = "lifeline"
    ADAPTIVE = "adaptive"


# Traced strategy codes: `simulator.SimParams` / `scheduler.SchedParams`
# carry the strategy as an int32 so ONE compiled program serves every
# strategy of a sweep grid, dispatched with `lax.switch`. The code order IS
# the dispatch-table order — every switch branch list below and in the
# executors must follow it.
GLOBAL_CODE, NEIGHBOR_CODE, LIFELINE_CODE, ADAPTIVE_CODE = range(4)
STRATEGY_CODES = {
    Strategy.GLOBAL: GLOBAL_CODE,
    Strategy.NEIGHBOR: NEIGHBOR_CODE,
    Strategy.LIFELINE: LIFELINE_CODE,
    Strategy.ADAPTIVE: ADAPTIVE_CODE,
}
CODE_STRATEGIES = {c: s for s, c in STRATEGY_CODES.items()}


def strategy_code(strategy) -> int:
    """Dispatch code of `strategy` (a Strategy, its value string, or an
    already-encoded int, passed through)."""
    if isinstance(strategy, Strategy):
        return STRATEGY_CODES[strategy]
    if isinstance(strategy, str):
        return STRATEGY_CODES[Strategy(strategy)]
    return int(strategy)


# Staging width of the grant/export path: the maximum number of bottom tasks
# a victim can hand out in one steal round. Single source of truth shared by
# `resolve_grants` callers, both deque backends' export (`deque.export_bottom`
# and the staged `deque.stage_export` the grant plan hands off to),
# `kernels.steal_compact` (its VMEM staging block is (block_w, GRANT_WIDTH,
# T)) and `kernels.ref.steal_compact_ref`; config budgets
# (`max_grants_per_victim`) must stay <= GRANT_WIDTH, asserted where the
# kernel is invoked.
GRANT_WIDTH = 8


class StealPlan(NamedTuple):
    victim: jax.Array   # (W,) int32 chosen victim, -1 for non-thieves
    rank: jax.Array     # (W,) int32 rank among same-victim requesters
    got: jax.Array      # (W,) bool steal granted
    taken: jax.Array    # (W,) int32 tasks taken from this worker (victim view)
    hops: jax.Array     # (W,) int32 thief→victim hop distance (latency model)


# --------------------------------------------------------------------------- #
# Victim-set tables (precomputed at init — paper §3.1 step 1)
# --------------------------------------------------------------------------- #
def neighbor_list(mesh: topo.MeshTopology) -> np.ndarray:
    """(W, 4) neighbor ids, NO_NEIGHBOR-padded (radius-1 victim set)."""
    return mesh.neighbor_table


def radius2_list(mesh: topo.MeshTopology) -> np.ndarray:
    """(W, 12) ids of workers within <=2 hops (excluding self), padded with -1.

    Coords-based and fully vectorized: enumerates the 12 Manhattan offsets of
    radius <= 2 instead of scanning the (W, W) hop matrix row by row, so
    building the ADAPTIVE victim table no longer blocks W >= 4k sweeps.
    Entries are ascending worker ids, deduplicated (small tori alias several
    offsets onto the same worker) — identical to the hop-matrix scan.
    """
    W = mesh.num_workers
    R, C = mesh.rows, mesh.cols
    offs = np.asarray([(dr, dc)
                       for dr in range(-2, 3) for dc in range(-2, 3)
                       if 0 < abs(dr) + abs(dc) <= 2], np.int64)   # (12, 2)
    r = mesh.coords[:, 0:1].astype(np.int64) + offs[None, :, 0]    # (W, 12)
    c = mesh.coords[:, 1:2].astype(np.int64) + offs[None, :, 1]
    if mesh.torus and W == R * C:  # the hop metric wraps only on exact tori
        r %= R
        c %= C
        ok = np.ones_like(r, bool)
    else:
        ok = (r >= 0) & (r < R) & (c >= 0) & (c < C)
    cand = np.where(ok, r * C + c, W)
    cand = np.where(cand >= W, W, cand)              # ragged last row
    cand = np.where(cand == np.arange(W)[:, None], W, cand)  # wraps onto self
    cand.sort(axis=1)
    dup = np.zeros_like(cand, bool)
    dup[:, 1:] = cand[:, 1:] == cand[:, :-1]
    cand[dup] = W
    cand.sort(axis=1)
    return np.where(cand == W, topo.NO_NEIGHBOR, cand).astype(np.int32)


def lifeline_list(num_workers: int, degree: int = 0) -> np.ndarray:
    """Hypercube lifelines: worker w's lifelines are w with one base-2 digit
    toggled (Saraswat et al. PPoPP'11), padded to a fixed width."""
    if degree == 0:
        degree = max(1, int(np.ceil(np.log2(max(num_workers, 2)))))
    out = np.full((num_workers, degree), topo.NO_NEIGHBOR, dtype=np.int32)
    for w in range(num_workers):
        k = 0
        for b in range(degree):
            partner = w ^ (1 << b)
            if partner < num_workers:
                out[w, k] = partner
                k += 1
    return out


# --------------------------------------------------------------------------- #
# Selection (vectorized; `key` is a per-round PRNG key shared SPMD-wide)
# --------------------------------------------------------------------------- #
def _pick_from_list(key, table: jax.Array, is_thief: jax.Array) -> jax.Array:
    """Uniform choice among valid (!= -1) entries of each worker's row."""
    W, K = table.shape
    valid = table != topo.NO_NEIGHBOR
    n_valid = jnp.maximum(valid.sum(axis=1), 1)
    r = jax.random.uniform(key, (W,))
    pick = jnp.minimum((r * n_valid).astype(jnp.int32), n_valid - 1)
    # index of the pick-th valid entry per row
    order = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1  # rank of each valid slot
    hit = valid & (order == pick[:, None])
    victim = jnp.max(jnp.where(hit, table, topo.NO_NEIGHBOR), axis=1)
    return jnp.where(is_thief & (victim >= 0), victim, topo.NO_NEIGHBOR)


def choose_global(key, num_workers: int, is_thief: jax.Array) -> jax.Array:
    """Uniform over all other workers (paper's global strategy)."""
    W = num_workers
    r = jax.random.randint(key, (W,), 0, max(W - 1, 1))
    me = jnp.arange(W)
    victim = jnp.where(r >= me, r + 1, r)  # uniform over {0..W-1}\{me}
    victim = jnp.clip(victim, 0, W - 1)
    return jnp.where(is_thief & (W > 1), victim, topo.NO_NEIGHBOR)


def choose_neighbor(key, neighbor_table: jax.Array, is_thief: jax.Array) -> jax.Array:
    """Uniform over the thief's directly connected neighbors (paper's contribution)."""
    return _pick_from_list(key, neighbor_table, is_thief)


def choose_lifeline(key, lifelines: jax.Array, fails: jax.Array,
                    num_workers: int, is_thief: jax.Array) -> jax.Array:
    """Try lifelines round-robin by fail count; fall back to global random."""
    W, L = lifelines.shape
    use_global = fails >= L
    k1, k2 = jax.random.split(key)
    slot = jnp.clip(fails, 0, L - 1)
    lane = lifelines[jnp.arange(W), slot]
    fallback = choose_global(k2, num_workers, is_thief)
    victim = jnp.where(use_global | (lane == topo.NO_NEIGHBOR), fallback, lane)
    return jnp.where(is_thief, victim, topo.NO_NEIGHBOR)


def choose_adaptive(key, neighbor_table: jax.Array, radius2_table: jax.Array,
                    fails: jax.Array, is_thief: jax.Array,
                    escalate_after: int = 4) -> jax.Array:
    """Neighbor-only, escalating to radius-2 after repeated failures
    (paper §6: 'gradually considering more distant victims')."""
    k1, k2 = jax.random.split(key)
    near = _pick_from_list(k1, neighbor_table, is_thief)
    far = _pick_from_list(k2, radius2_table, is_thief)
    return jnp.where(is_thief & (fails >= escalate_after), far, near)


def cheapest_live_table(neighbor_table: jax.Array,
                        link_tau: jax.Array) -> jax.Array:
    """Mask `neighbor_table` down to the τ-argmin set of each worker's live
    neighbors (NO_NEIGHBOR elsewhere). Single source of truth for the
    link-aware ADAPTIVE near pick — shared by `choose_adaptive_linkaware`,
    `batched_victim_draws` and the famine replay's `victim_draws_code`
    table, so the replay can never drift from the per-tick preference
    rule."""
    valid = neighbor_table != topo.NO_NEIGHBOR
    cost = jnp.where(valid, link_tau, jnp.iinfo(jnp.int32).max)
    cheapest = valid & (cost == jnp.min(cost, axis=1, keepdims=True))
    return jnp.where(cheapest, neighbor_table, topo.NO_NEIGHBOR)


def mask_reachable(table: jax.Array, comp_row: jax.Array) -> jax.Array:
    """Mask a (W, D) victim table down to same-live-link-component entries
    (NO_NEIGHBOR elsewhere). Works against either routing backend — both
    dense and sparse tables carry identical per-epoch component rows. The
    single spelling shared by the simulator's escalated-draw masking and
    the famine horizon, so reachability can never drift between them."""
    W = comp_row.shape[0]
    ok = ((table != topo.NO_NEIGHBOR)
          & (comp_row[jnp.clip(table, 0, W - 1)] == comp_row[:, None]))
    return jnp.where(ok, table, topo.NO_NEIGHBOR)


def choose_adaptive_linkaware(key, neighbor_table: jax.Array,
                              radius2_table: jax.Array, link_tau: jax.Array,
                              fails: jax.Array, is_thief: jax.Array,
                              escalate_after: int = 4) -> jax.Array:
    """ADAPTIVE under a time-varying link state: prefer the *cheapest* live
    neighbor (uniform among the current-τ argmin set, so a uniform schedule
    reduces exactly to `choose_adaptive`), escalating to radius-2 after
    `escalate_after` consecutive failures. `neighbor_table` must already
    have dead links masked to NO_NEIGHBOR; `link_tau` is the (W, 4) row of
    the active epoch."""
    k1, k2 = jax.random.split(key)
    near = _pick_from_list(k1, cheapest_live_table(neighbor_table, link_tau),
                           is_thief)
    far = _pick_from_list(k2, radius2_table, is_thief)
    return jnp.where(is_thief & (fails >= escalate_after), far, near)


# --------------------------------------------------------------------------- #
# Conflict resolution (shared by all strategies and both executors)
# --------------------------------------------------------------------------- #
def segment_prefix(key: jax.Array, active: jax.Array,
                   weights: jax.Array | None = None,
                   priority: jax.Array | None = None) -> jax.Array:
    """Exclusive prefix sum of `weights` within equal-`key` segments.

    Workers are ordered inside a segment by (priority, worker id); worker
    w's result is the sum of the weights of same-key active workers that
    precede it. Sort-based: O(W log W) and never materializes a (W, W)
    intermediate — the shared primitive behind `resolve_grants` service
    ranks (unit weights) and the simulator's multi-source transplant
    insertion offsets (deque-size weights).

    Args:
      key: (W,) int segment id per worker (e.g. chosen victim, heir).
      active: (W,) bool — inactive workers sort last and return 0.
      weights: (W,) int summands; defaults to ones (prefix = rank).
      priority: (W,) optional within-segment order (lower = first);
        worker id breaks ties. Defaults to worker id.
    """
    W = key.shape[0]
    ids = jnp.arange(W)
    if weights is None:
        weights = jnp.ones((W,), jnp.int32)
    if priority is None:
        priority = ids
    skey = jnp.where(active, key, W)  # inactive → sentinel segment, sorts last
    # lexsort is keyed last-to-first; the id key makes the order total, so no
    # reliance on sort stability.
    order = jnp.lexsort((ids, priority, skey))
    skey_sorted = skey[order]
    w_sorted = jnp.where(active, weights, 0)[order].astype(jnp.int32)
    excl = jnp.cumsum(w_sorted) - w_sorted  # global exclusive prefix
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), skey_sorted[1:] != skey_sorted[:-1]])
    seg_first = jax.lax.cummax(jnp.where(is_start, ids, 0))
    prefix_sorted = excl - excl[seg_first]  # subtract segment base
    prefix = jnp.zeros((W,), jnp.int32).at[order].set(prefix_sorted)
    return jnp.where(active, prefix, 0)


def resolve_grants(victim: jax.Array, sizes: jax.Array,
                   max_grants_per_victim: int = 4,
                   priority: jax.Array | None = None) -> StealPlan:
    """Deterministically match thieves to victim deque-bottom slots.

    Sort-based segment ranking (O(W log W), no (W, W) intermediates);
    bit-identical to `resolve_grants_pairwise`, the O(W^2) reference kept
    for the equivalence property test.

    Args:
      victim: (W,) chosen victim per worker, NO_NEIGHBOR for non-thieves.
      sizes: (W,) current deque sizes (post owner activity this round).
      max_grants_per_victim: per-round response budget of a victim (the
        bulk-synchronous stand-in for the victim serializing requests).
      priority: (W,) optional tie-break order (lower = served first);
        defaults to worker id.

    Returns a StealPlan; `rank[w]` is w's position in its victim's service
    order, `got[w]` whether a task is granted (rank < min(size, budget)),
    `taken[v]` how many tasks leave victim v's bottom this round.
    """
    W = victim.shape[0]
    req = victim >= 0
    rank = segment_prefix(victim, req, priority=priority)
    vsize = jnp.where(req, sizes[jnp.clip(victim, 0, W - 1)], 0)
    budget = jnp.minimum(vsize, max_grants_per_victim)
    got = req & (rank < budget)
    taken = jnp.zeros((W,), jnp.int32).at[jnp.clip(victim, 0, W - 1)].add(
        got.astype(jnp.int32))
    return StealPlan(victim=jnp.where(req, victim, topo.NO_NEIGHBOR),
                     rank=rank, got=got, taken=taken,
                     hops=jnp.zeros((W,), jnp.int32))


def resolve_grants_pairwise(victim: jax.Array, sizes: jax.Array,
                            max_grants_per_victim: int = 4,
                            priority: jax.Array | None = None) -> StealPlan:
    """O(W^2) pairwise-rank reference for `resolve_grants` (test oracle only).

    Builds the full same-victim comparison matrix; kept out of every hot
    path but asserted equivalent to the sorted implementation over random
    victim/priority/size vectors in the test suite.
    """
    W = victim.shape[0]
    req = victim >= 0
    if priority is None:
        priority = jnp.arange(W)
    same = (victim[:, None] == victim[None, :]) & req[:, None] & req[None, :]
    ahead = same & (
        (priority[None, :] < priority[:, None])
        | ((priority[None, :] == priority[:, None])
           & (jnp.arange(W)[None, :] < jnp.arange(W)[:, None]))
    )
    rank = jnp.sum(ahead, axis=1).astype(jnp.int32)
    vsize = jnp.where(req, sizes[jnp.clip(victim, 0, W - 1)], 0)
    budget = jnp.minimum(vsize, max_grants_per_victim)
    got = req & (rank < budget)
    taken = jnp.zeros((W,), jnp.int32).at[jnp.clip(victim, 0, W - 1)].add(
        got.astype(jnp.int32))
    taken = jnp.where(jnp.arange(W) >= 0, taken, 0)  # shape anchor
    return StealPlan(victim=jnp.where(req, victim, topo.NO_NEIGHBOR),
                     rank=rank, got=got, taken=taken,
                     hops=jnp.zeros((W,), jnp.int32))


# --------------------------------------------------------------------------- #
# Famine fast path support (simulator's probe-cycle leaping)
# --------------------------------------------------------------------------- #
def _any_nonempty(table: jax.Array, nonempty: jax.Array) -> jax.Array:
    """Per-worker: does any valid (!= NO_NEIGHBOR) entry of `table` index a
    worker with a nonempty deque?"""
    W = nonempty.shape[0]
    valid = table != topo.NO_NEIGHBOR
    hit = nonempty[jnp.clip(table, 0, W - 1)] & valid
    return hit.any(axis=1)


def probe_may_succeed(strategy: Strategy, nonempty: jax.Array,
                      fails: jax.Array, neighbor_table: jax.Array,
                      radius2_table: jax.Array | None, *,
                      escalate_after: int, window: int, min_cycle,
                      num_workers: int,
                      comp_row: jax.Array | None = None) -> jax.Array:
    """Deterministic per-worker emptiness/reachability predicate.

    Returns, per worker, whether a steal probe *drawn within the next
    `window` ticks* could land on a victim whose deque is nonempty right
    now. Where this is False — and deque sizes are provably frozen over the
    window, which the simulator's famine horizon guarantees — every probe
    the worker issues in the window must fail, so whole probe cycles can be
    advanced analytically instead of simulated tick by tick (the
    lifeline-graph insight: victim emptiness is deterministic between
    events). With open-loop traffic (`core/arrivals.py`) the simulator
    clips the certified window at the next arrival-candidate tick — an
    accepted candidate grows a deque, breaking the frozen-sizes premise —
    so this predicate never needs to know about arrivals.

    `neighbor_table` (and, for ADAPTIVE, `radius2_table`) must already have
    dead links / unreachable victims masked to NO_NEIGHBOR when running
    under a link-state schedule. For GLOBAL, `comp_row` — the active
    epoch's (W,) live-link connected-component ids — restricts the
    predicate to *reachable* nonempty victims (a probe to a different
    component never departs, so it can never succeed): without it any
    nonempty deque anywhere keeps every GLOBAL thief risky. For ADAPTIVE
    the radius-2 set only matters if the worker can escalate inside the
    window: each failed attempt costs at least `min_cycle` ticks
    (2·τ_min − 1), so a worker needing k more failures to escalate cannot
    draw a radius-2 victim before (k − 1)·min_cycle ticks have passed.
    LIFELINE falls back to global-random victims, so it is always treated
    as able to succeed (the simulator keeps it on the slow path).
    """
    if strategy == Strategy.GLOBAL:
        if comp_row is None:
            return jnp.broadcast_to(nonempty.any() & (num_workers > 1),
                                    (num_workers,))
        in_comp = jnp.zeros((num_workers,), jnp.int32).at[comp_row].add(
            nonempty.astype(jnp.int32))
        others = in_comp[comp_row] - nonempty.astype(jnp.int32)
        return others > 0
    if strategy == Strategy.LIFELINE:
        return jnp.ones((num_workers,), bool)
    near = _any_nonempty(neighbor_table, nonempty)
    if strategy == Strategy.NEIGHBOR:
        return near
    if strategy == Strategy.ADAPTIVE:
        to_go = escalate_after - fails
        may_escalate = (to_go - 1) * min_cycle < window
        return near | (_any_nonempty(radius2_table, nonempty) & may_escalate)
    raise ValueError(strategy)


def probe_may_succeed_code(code, nonempty: jax.Array, fails: jax.Array,
                           neighbor_table: jax.Array,
                           radius2_table: jax.Array, *,
                           escalate_after, window: int, min_cycle,
                           num_workers: int,
                           comp_row: jax.Array | None = None) -> jax.Array:
    """Traced-strategy `probe_may_succeed`: `code` is an int32 strategy code
    and `escalate_after` / `min_cycle` may be traced scalars, so one compiled
    famine horizon serves a whole sweep grid. Every strategy's predicate is
    computed (cheap row reductions) and the code-selected one returned —
    bit-identical to the enum version per strategy (asserted in tests).
    `radius2_table` is required (the grid program can always select
    ADAPTIVE); LIFELINE still answers all-True, keeping it off the fast
    path."""
    W = num_workers
    if comp_row is None:
        glob = jnp.broadcast_to(nonempty.any() & (W > 1), (W,))
    else:
        in_comp = jnp.zeros((W,), jnp.int32).at[comp_row].add(
            nonempty.astype(jnp.int32))
        glob = (in_comp[comp_row] - nonempty.astype(jnp.int32)) > 0
    near = _any_nonempty(neighbor_table, nonempty)
    to_go = escalate_after - fails
    may_escalate = (to_go - 1) * min_cycle < window
    adapt = near | (_any_nonempty(radius2_table, nonempty) & may_escalate)
    return jnp.where(code == GLOBAL_CODE, glob,
                     jnp.where(code == NEIGHBOR_CODE, near,
                               jnp.where(code == ADAPTIVE_CODE, adapt,
                                         jnp.ones((W,), bool))))


def victim_draws_code(code, key0: jax.Array, t, neighbor_table: jax.Array,
                      radius2_table: jax.Array, *, num_workers: int,
                      adaptive_table: jax.Array | None = None):
    """One tick's victim draws for an all-thieves mask, dispatched over an
    int32 strategy code with `lax.switch`: ``(near, far)``, each (W,), for
    tick `t`. `far` duplicates `near` for the single-draw strategies, so the
    caller's escalation select reduces to the near draw. Each branch uses
    the key exactly as its per-tick `_select` counterpart (same splits, same
    ``fold_in(key0, t)`` schedule), so the famine replay, which draws one
    tick at a time with this function, stays bit-identical to the one-tick
    oracle. `adaptive_table` is ADAPTIVE's near set, `neighbor_table` when
    omitted; under a link-state schedule the caller passes
    `cheapest_live_table(neighbor_table, link_tau_row)`, built once per
    epoch rather than once per draw. The LIFELINE branch returns global
    draws as a placeholder: the simulator's famine path is predicate-gated
    off for LIFELINE, so the branch output can only be produced — and then
    discarded — under vmapped-switch execute-all-branches semantics."""
    W = num_workers
    all_thieves = jnp.ones((W,), bool)
    key = jax.random.fold_in(key0, t)
    near_tab = neighbor_table if adaptive_table is None else adaptive_table

    def b_global(_):
        near = choose_global(key, W, all_thieves)
        return near, near

    def b_neighbor(_):
        near = choose_neighbor(key, neighbor_table, all_thieves)
        return near, near

    def b_adaptive(_):
        k1, k2 = jax.random.split(key)
        return (_pick_from_list(k1, near_tab, all_thieves),
                _pick_from_list(k2, radius2_table, all_thieves))

    # dispatch-table order == the strategy code order
    return jax.lax.switch(code, [b_global, b_neighbor, b_global, b_adaptive],
                          None)


def batched_victim_draws(strategy: Strategy, key0: jax.Array, t0, count: int,
                         neighbor_table: jax.Array,
                         radius2_table: jax.Array | None, *,
                         num_workers: int, link_tau_row: jax.Array | None = None):
    """Replay `count` consecutive ticks' victim draws in one batch, per
    strategy enum — the oracle the tests hold `victim_draws_code` to.

    Returns ``(near, far)`` of shape (count, W): row j holds the victims
    the per-tick selection would draw at tick ``t0 + j`` for an
    all-thieves mask. Randomness stays ``fold_in(key0, t)``-keyed — the
    same key schedule the simulator's one-tick path uses — so gathering
    row ``t − t0`` reproduces that tick's draw bit-for-bit. `far` is None
    except for ADAPTIVE, whose caller selects per worker between the near
    and escalated draw by its fail count at probe time. Under a link-state
    schedule pass the masked `neighbor_table` and, for ADAPTIVE, the active
    epoch's `link_tau_row` (cheapest-live-neighbor preference).
    """
    W = num_workers
    all_thieves = jnp.ones((W,), bool)
    ticks = t0 + jnp.arange(count)
    keys = jax.vmap(lambda t: jax.random.fold_in(key0, t))(ticks)
    if strategy == Strategy.GLOBAL:
        near = jax.vmap(lambda k: choose_global(k, W, all_thieves))(keys)
        return near, None
    if strategy == Strategy.NEIGHBOR:
        near = jax.vmap(
            lambda k: choose_neighbor(k, neighbor_table, all_thieves))(keys)
        return near, None
    if strategy == Strategy.ADAPTIVE:
        near_tab = (neighbor_table if link_tau_row is None
                    else cheapest_live_table(neighbor_table, link_tau_row))

        def draw(k):
            k1, k2 = jax.random.split(k)
            return (_pick_from_list(k1, near_tab, all_thieves),
                    _pick_from_list(k2, radius2_table, all_thieves))
        near, far = jax.vmap(draw)(keys)
        return near, far
    raise ValueError(f"no batched draws for {strategy}")


def attach_hops(plan: StealPlan, mesh) -> StealPlan:
    """Fill in thief→victim hop distances (for the latency simulator).

    `mesh` is a `topology.MeshTopology`; distances are priced from the
    (W, 2) coordinate table via `topology.hop_dist`, so no dense (W, W)
    pairwise array is ever materialized (it used to be — the last consumer
    of that matrix outside tests). Passing the dense distance matrix itself
    is deprecated and kept only so tests can cross-check against the
    `topology` oracle.
    """
    W = plan.victim.shape[0]
    if isinstance(mesh, topo.MeshTopology):
        hops = topo.hop_dist(mesh, jnp.asarray(mesh.coords), plan.victim)
    else:
        import warnings

        warnings.warn(
            "attach_hops(plan, <dense distance matrix>) is deprecated; pass "
            "the MeshTopology instead (hops are priced from coordinates)",
            DeprecationWarning, stacklevel=2)
        v = jnp.clip(plan.victim, 0, W - 1)
        hops = jnp.asarray(mesh)[jnp.arange(W), v].astype(jnp.int32)
    return plan._replace(hops=jnp.where(plan.victim >= 0, hops, 0))
