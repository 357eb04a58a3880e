"""Time-varying link-state schedules for the 2D-mesh simulator (paper §2.1).

LEO inter-satellite links are not uniform: inter-plane ISL latency oscillates
with orbital phase (adjacent planes converge near the poles), links drop out
predictably (satellites powering down in eclipse, cross-seam handovers
between counter-rotating planes), and individual satellites run degraded.
`repro.core.constellation` knows the orbital mechanics; this module defines
the *contract* between it and the simulator: a compiled, piecewise-constant
`LinkStateSchedule` of plain arrays, so the simulator itself stays
orbital-mechanics-free.

Model
-----
Time is split into epochs at `epoch_starts` (int ticks, starting at 0);
epoch `e` covers ``[epoch_starts[e], epoch_starts[e+1])`` and the last epoch
extends forever. Within an epoch every quantity is constant:

  * ``link_tau[e, w, d]`` — one-hop latency (ticks, >= 1) of worker `w`'s
    link in mesh direction `d` (`topology.DIRECTIONS` order: N, S, W, E).
    Links are undirected: the value must match the reverse entry on the
    neighbor's side (checked by `validate`).
  * ``link_up[e, w, d]`` — whether that link is usable. A down link removes
    the neighbor from radius-1 victim selection (NEIGHBOR / ADAPTIVE): the
    outage is *predictable*, so thieves do not waste probes on it. Multi-hop
    flights (GLOBAL / LIFELINE / escalated ADAPTIVE) are assumed to be
    routed around outages by the network layer and see only latency.
  * ``speed[e, w]`` — straggler divisor per worker (1 = nominal), letting
    degradation vary over the orbit (thermal throttling, battery saving).

Message flights are priced by dimension-order routing (rows first in the
source's column, then columns in the destination's row): the flight departs
at tick `t` and its duration is the sum of per-link `link_tau` along that
path in the epoch containing `t` — latency is locked at launch; an epoch
change mid-flight does not retime messages already in transit. On a full
torus the shorter ring arc (by hop count, ties to the non-wrapping side) is
used per axis, matching the simulator's `_hop_dist` hop accounting.

`device_tables` compiles a schedule into `LinkStateArrays` — jnp arrays plus
per-epoch prefix sums over both mesh axes — so `flight_ticks` prices any
flight with O(1) gathers and the per-tick path never materializes a (W, W)
intermediate. The simulator's event-leaping stepper adds `next_change` as a
horizon term so a leap never jumps across an epoch boundary, which keeps
``step_mode="leap"`` bit-identical to the one-tick oracle under dynamic
schedules (asserted in tests/test_simulator.py).

Route-around (detour pricing during outages)
--------------------------------------------
Dimension-order pricing assumes its path is live. Epochs in which any
existing link is DOWN (seam handovers, eclipse darkness) instead price
flights from a per-epoch all-pairs shortest-path table over *live* links
only, built once at `device_tables` time by `live_path_costs` (vectorized
repeated min-plus relaxation over the 4-neighbor mesh — asserted against
the dense Floyd–Warshall oracle `topology.detour_matrix`). Epochs with the
same (τ, up) link state share one table, and all-up epochs build none at
all — they keep the exact dimension-order prefix-sum costs, so a static or
outage-free schedule is priced bit-identically to before. Per-epoch
connected-component ids (`comp`) expose reachability without any (W, W)
work at simulation time: a flight to a different component never departs
(the thief's routing layer knows the victim is unreachable), and the
simulator masks unreachable victims out of escalated (radius-2) selection
and out of the famine-window emptiness predicate. Pairs with no live route
are pinned at `UNREACHABLE` in the tables; `flight_ticks` itself falls
back to the dimension-order cost for such pairs (callers gate departures
on `same_component`, so the fallback is only ever consumed by a reply
whose path was severed by an epoch flip mid-request — the thief waits out
the nominal RTT as a timeout while the grant is denied).

Sparse hierarchical routing (``routing="sparse"``)
--------------------------------------------------
The dense tables above cost O(W²) bytes per distinct outage link state —
~1 GiB per table row at W = 16384 — which caps *dynamic* runs far below
the full-constellation regime. The sparse backend replaces them with a
two-level scheme costing O(W·L) per row:

  * the grid is tiled into rectangular **patches** (`topology.patch_dims`,
    ≤ half the axis each so same-patch ring arcs never wrap);
  * **within a patch** whose internal links are all live (`patch_clean`),
    flights keep the exact dimension-order prefix-sum price (every link
    the path crosses has both endpoints inside the patch);
  * **across patches** (or inside a dirty patch), flights are priced via
    **landmarks** — one per patch (its center worker) plus one
    representative per otherwise-uncovered live component — using the
    per-epoch landmark→worker shortest-path vectors `lm_cost` over live
    links only: ``cost(s, d) = min_ℓ lm[ℓ, s] + lm[ℓ, d]``.

Guarantee (oracle-checked against `topology.detour_matrix` in tests): for
any same-component pair, the sparse price is **at least** the true live
shortest-path cost (every estimate is the cost of a real live path) and
**at most** ``true + 2ρ``, where ρ is the epoch's maximum over landmark-
covered workers of the distance to their nearest landmark (reported as
``stretch_add = 2ρ_max`` in the build stats; triangle inequality through
the source's nearest landmark). Same-patch pairs in clean patches take
``min(dimension-order, landmark)``, which is *exact* whenever the
in-patch dimension-order path is a live shortest path — in particular
under uniform τ (the hop metric's shorter arc IS the cheapest); with
per-boundary oscillating τ a wrap arc outside the patch can undercut it
by a few ticks, in which case the pair is still covered by the 2ρ bound.
Component ids are identical to the dense backend's by construction
(lowest reachable worker id), so reachability gating, victim-set masking,
and the famine-window emptiness predicate are backend-independent;
unreachable pairs fall back to the dimension-order timeout price exactly
as under the dense backend.

Epoch dedup is two-level under either backend: the **structural** half
(component ids, patch cleanliness, landmark choice) is keyed on `link_up`
alone and reused when only τ oscillates; the **cost** half (detour /
landmark tables) is keyed on the full (τ, up) state. `build_tables`
reports both hit counts plus table bytes, build seconds, and the
dense-equivalent byte count in a `RoutingBuildStats`.
"""

from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import topology as topo

from scipy.sparse import csr_matrix as _csr
from scipy.sparse.csgraph import (connected_components as _scipy_cc,
                                  dijkstra as _scipy_dijkstra)

# Direction indices into topology.DIRECTIONS ((-1,0),(1,0),(0,-1),(0,1)).
NORTH, SOUTH, WEST, EAST = range(topo.NUM_DIRECTIONS)
OPPOSITE = (SOUTH, NORTH, EAST, WEST)

# Cost sentinel for worker pairs with no live route (shared with the dense
# topology.detour_matrix oracle).
UNREACHABLE = topo.UNREACHABLE

# Landmark vectors are stored as uint16 to halve the resident bytes of the
# (K, L, W) tables at W >= 16k; this is the stored no-route sentinel, mapped
# back to UNREACHABLE at gather time. Real live-path costs are bounded by
# (R + C) · τ_max, far below 2^16 - 1 (validated at build time).
_LM_INF = np.uint16(0xFFFF)

# Auto routing policy: meshes at or above this worker count get the sparse
# backend (dense tables would cost W² · 4 bytes per outage class — 64 MiB at
# W = 4096, 1 GiB at W = 16384); smaller meshes keep the exact dense tables.
SPARSE_AUTO_MIN_WORKERS = 4096


def resolve_routing(routing: str, num_workers: int) -> str:
    """Resolve a ``routing`` argument ('auto' | 'dense' | 'sparse')."""
    if routing == "auto":
        return ("sparse" if num_workers >= SPARSE_AUTO_MIN_WORKERS
                else "dense")
    if routing not in ("dense", "sparse"):
        raise ValueError(
            f"routing must be 'auto', 'dense', or 'sparse', got {routing!r}")
    return routing


@dataclasses.dataclass(frozen=True)
class LinkStateSchedule:
    """Piecewise-constant link state, plain numpy (host-side)."""

    epoch_starts: np.ndarray   # (E,) int32, epoch_starts[0] == 0, increasing
    link_tau: np.ndarray       # (E, W, 4) int32 one-hop latency, >= 1
    link_up: np.ndarray        # (E, W, 4) bool
    speed: np.ndarray          # (E, W) int32 straggler divisors, >= 1

    # ------------------------------------------------------------------ #
    # Host-side queries
    # ------------------------------------------------------------------ #
    @property
    def num_epochs(self) -> int:
        return int(self.epoch_starts.shape[0])

    def epoch_of(self, t: int) -> int:
        return int(np.searchsorted(self.epoch_starts, t, side="right") - 1)

    def tau_at(self, t: int) -> np.ndarray:
        """(W, 4) link latencies active at tick `t`."""
        return self.link_tau[self.epoch_of(t)]

    def up_at(self, t: int) -> np.ndarray:
        """(W, 4) link availability active at tick `t`."""
        return self.link_up[self.epoch_of(t)]

    def speed_at(self, t: int) -> np.ndarray:
        return self.speed[self.epoch_of(t)]

    def mean_tau(self, mesh: topo.MeshTopology, horizon_ticks: int) -> float:
        """Duration-weighted mean latency of existing links over `horizon`.

        The single scalar a static-τ baseline would collapse this schedule
        to — used by benchmarks for the static-vs-dynamic comparison.
        """
        starts = self.epoch_starts.astype(np.int64)
        ends = np.append(starts[1:], max(horizon_ticks, int(starts[-1]) + 1))
        spans = np.maximum(ends - starts, 0).astype(np.float64)  # (E,)
        exists = mesh.neighbor_table != topo.NO_NEIGHBOR         # (W, 4)
        per_epoch = (self.link_tau * exists[None]).sum(axis=(1, 2)) / max(
            exists.sum(), 1)
        return float((per_epoch * spans).sum() / max(spans.sum(), 1.0))

    # ------------------------------------------------------------------ #
    # Validation / constructors
    # ------------------------------------------------------------------ #
    def validate(self, mesh: topo.MeshTopology) -> "LinkStateSchedule":
        E = self.num_epochs
        W = mesh.num_workers
        if self.epoch_starts.shape != (E,) or E == 0:
            raise ValueError("epoch_starts must be a non-empty 1D array")
        if int(self.epoch_starts[0]) != 0:
            raise ValueError("epoch_starts must begin at tick 0")
        if E > 1 and not (np.diff(self.epoch_starts) > 0).all():
            raise ValueError("epoch_starts must be strictly increasing")
        if self.link_tau.shape != (E, W, topo.NUM_DIRECTIONS):
            raise ValueError(f"link_tau must be (E, W, 4), got {self.link_tau.shape}")
        if self.link_up.shape != (E, W, topo.NUM_DIRECTIONS):
            raise ValueError(f"link_up must be (E, W, 4), got {self.link_up.shape}")
        if self.speed.shape != (E, W):
            raise ValueError(f"speed must be (E, W), got {self.speed.shape}")
        if (self.link_tau < 1).any():
            raise ValueError("link_tau entries must be >= 1 tick")
        if (self.speed < 1).any():
            raise ValueError("speed divisors must be >= 1")
        # undirected links: each existing link must agree with its reverse
        nbr = mesh.neighbor_table                                 # (W, 4)
        nbr_c = np.clip(nbr, 0, W - 1)
        for d in range(topo.NUM_DIRECTIONS):
            has = nbr[:, d] != topo.NO_NEIGHBOR
            rev_tau = self.link_tau[:, nbr_c[:, d], OPPOSITE[d]]
            rev_up = self.link_up[:, nbr_c[:, d], OPPOSITE[d]]
            if (has & (self.link_tau[:, :, d] != rev_tau)).any():
                raise ValueError(f"asymmetric link_tau along direction {d}")
            if (has & (self.link_up[:, :, d] != rev_up)).any():
                raise ValueError(f"asymmetric link_up along direction {d}")
        return self

    @staticmethod
    def static(mesh: topo.MeshTopology, tau: int,
               speed: np.ndarray | None = None) -> "LinkStateSchedule":
        """Single-epoch uniform schedule: τ everywhere, all links up.

        `simulate(..., linkstate=static(mesh, τ))` is bit-identical to the
        scalar ``hop_ticks=τ`` path (asserted in tests) — the degenerate
        case the pre-linkstate simulator hard-coded.
        """
        W = mesh.num_workers
        sp = (np.ones((1, W), np.int32) if speed is None
              else np.asarray(speed, np.int32).reshape(1, W))
        return LinkStateSchedule(
            epoch_starts=np.zeros(1, np.int32),
            link_tau=np.full((1, W, topo.NUM_DIRECTIONS), int(tau), np.int32),
            link_up=np.ones((1, W, topo.NUM_DIRECTIONS), bool),
            speed=sp,
        ).validate(mesh)


class LinkStateArrays(NamedTuple):
    """Device-side view of a schedule, consumed inside `lax.while_loop`.

    `cum_v[e, k, c]` is the prefix sum of southward link latencies of rows
    `< k` in column `c` (row `R-1` holds the ring-wrap link), `cum_h` the
    eastward analogue — dimension-order path costs become two gather-diffs.

    `detour` holds one (W, W) live-link shortest-path table per *distinct
    outage link state* (epochs with identical (τ, up) arrays share a row;
    `None` when no epoch has a dead link — the static/all-up case costs
    nothing). `detour_idx[e]` maps an epoch to its table row (-1 = all
    links up: dimension-order pricing applies). `comp[e, w]` is worker w's
    live-link connected-component id in epoch e (the lowest reachable
    worker id; all zeros for all-up epochs) — the O(W)-gather reachability
    primitive behind departure gating and victim-set masking. All tables
    are compiled once per schedule; flights gather from them without ever
    materializing a (W, W) intermediate per tick.

    Under the sparse hierarchical backend (module docstring) `detour` is
    None and outage epochs instead carry `lm_cost[k, l, w]` — uint16
    landmark→worker live shortest-path costs (`_LM_INF` = no route /
    padding landmark), row k shared across epochs exactly like a dense
    table row — plus the static patch partition `patch_id[w]` and the
    per-class patch cleanliness flags `patch_clean[k, p]` (no dead link
    with both endpoints inside patch p). `detour_idx` and `comp` keep the
    same meaning for both backends.
    """
    epoch_starts: jax.Array   # (E,)
    link_tau: jax.Array       # (E, W, 4)
    link_up: jax.Array        # (E, W, 4)
    speed: jax.Array          # (E, W)
    cum_v: jax.Array          # (E, R+1, C)
    cum_h: jax.Array          # (E, R, C+1)
    detour: jax.Array | None  # (K, W, W) or None (no outage epochs / sparse)
    detour_idx: jax.Array     # (E,) row into the cost tables, -1 = all-up
    comp: jax.Array           # (E, W) connected-component ids (live links)
    # sparse hierarchical backend only (None under dense / no outages)
    lm_cost: jax.Array | None = None      # (K, L, W) uint16 landmark costs
    patch_id: jax.Array | None = None     # (W,) int32 patch index
    patch_clean: jax.Array | None = None  # (K, P) bool


def has_outage_tables(tbl: LinkStateArrays) -> bool:
    """Trace-time: does this schedule carry outage-epoch routing tables
    (dense detour rows or sparse landmark vectors)? The predicate every
    simulator-side `detour is None` check generalizes to, so the sparse
    backend flows through the same reachability/masking paths."""
    return tbl.detour is not None or tbl.lm_cost is not None


def table_bytes(tbl: LinkStateArrays) -> int:
    """Resident bytes of the outage-routing tables (host view): the cost
    tables (dense detour rows or sparse landmark vectors + patch flags)
    plus the per-epoch component rows and the epoch→row index."""
    n = tbl.detour_idx.size * 4 + tbl.comp.size * 4
    if tbl.detour is not None:
        n += tbl.detour.size * 4
    if tbl.lm_cost is not None:
        n += tbl.lm_cost.size * 2 + tbl.patch_clean.size + tbl.patch_id.size * 4
    return int(n)


@dataclasses.dataclass(frozen=True)
class RoutingBuildStats:
    """Build report of `build_tables` (host-side observability)."""
    routing: str               # "dense" | "sparse" (resolved)
    num_epochs: int
    outage_epochs: int
    struct_classes: int        # distinct link_up states among outage epochs
    cost_classes: int          # distinct (τ, up) states among outage epochs
    struct_dedup_hits: int     # outage epochs that reused a struct class
    cost_dedup_hits: int       # outage epochs that reused a cost class
    table_bytes: int           # resident routing-table bytes (see table_bytes)
    dense_equiv_bytes: int     # cost_classes · W² · 4 — what dense would cost
    build_seconds: float
    num_landmarks: int = 0     # sparse: padded landmark count L
    num_patches: int = 0       # sparse: patch count P
    patch_shape: tuple[int, int] = (0, 0)
    stretch_add: int = 0       # sparse: max additive stretch 2ρ over classes


def live_path_costs(mesh: topo.MeshTopology, tau_row: np.ndarray,
                    up_row: np.ndarray) -> np.ndarray:
    """(W, W) all-pairs shortest-path costs over live links, host-side.

    Vectorized repeated min-plus relaxation over the 4-neighbor mesh: each
    sweep relaxes every live edge at once (four (W, W) gathers), converging
    in at most diameter-of-the-live-graph sweeps — no Python loop over
    workers, no O(W^3) Floyd–Warshall (that stays in `topology.detour_matrix`
    as the test oracle). Unreachable pairs are pinned at `UNREACHABLE`.
    """
    W = mesh.num_workers
    inf = np.int64(1) << 40
    nbr = mesh.neighbor_table
    nbr_c = np.clip(nbr, 0, W - 1)
    live = (nbr != topo.NO_NEIGHBOR) & np.asarray(up_row, bool)
    tau = np.asarray(tau_row, np.int64)
    d = np.full((W, W), inf, np.int64)
    np.fill_diagonal(d, 0)
    for _ in range(W):  # converges in <= longest live shortest path sweeps
        nd = d
        for k in range(topo.NUM_DIRECTIONS):
            cand = np.where(live[:, k, None], tau[:, k, None] + d[nbr_c[:, k]],
                            inf)
            nd = np.minimum(nd, cand)
        if (nd == d).all():
            break
        d = nd
    return np.minimum(d, UNREACHABLE).astype(np.int32)


def _live_graph(mesh: topo.MeshTopology, tau_row, up_row):
    """Directed (both arcs present) edge list of the live link graph."""
    nbr = mesh.neighbor_table
    live = (nbr != topo.NO_NEIGHBOR) & np.asarray(up_row, bool)
    src, d = np.nonzero(live)
    return src, nbr[src, d], np.asarray(tau_row)[src, d].astype(np.int64)


def live_components(mesh: topo.MeshTopology, up_row: np.ndarray) -> np.ndarray:
    """(W,) live-link connected-component ids, labeled by each component's
    lowest worker id — identical to the dense backend's
    ``argmax(live_path_costs < UNREACHABLE, axis=1)`` labeling, without any
    (W, W) work (scipy's union-find)."""
    W = mesh.num_workers
    src, dst, _ = _live_graph(mesh, np.ones((W, 4), np.int64), up_row)
    g = _csr((np.ones(len(src), np.int8), (src, dst)), shape=(W, W))
    _, labels = _scipy_cc(g, directed=False)
    lowest = np.full(labels.max() + 1 if W else 1, W, np.int64)
    np.minimum.at(lowest, labels, np.arange(W))
    return lowest[labels].astype(np.int32)


def landmark_costs(mesh: topo.MeshTopology, tau_row: np.ndarray,
                   up_row: np.ndarray, landmarks: np.ndarray) -> np.ndarray:
    """(L, W) int32 shortest-path costs landmark → every worker over LIVE
    links (UNREACHABLE where no route), by scipy's multi-source Dijkstra:
    O(L·W·polylog), never O(W²)."""
    W = mesh.num_workers
    L = len(landmarks)
    if L == 0:
        return np.empty((0, W), np.int32)
    src, dst, wts = _live_graph(mesh, tau_row, up_row)
    g = _csr((wts.astype(np.float64), (src, dst)), shape=(W, W))
    d = _scipy_dijkstra(g, directed=True, indices=np.asarray(landmarks))
    d = d.reshape(L, W)
    return np.where(np.isfinite(d), d, float(UNREACHABLE)).astype(np.int32)


class _StructClass:
    """Per-distinct-`link_up` routing structure, reused across τ-only
    oscillation (the structural half of the two-level epoch dedup)."""

    __slots__ = ("comp", "covered", "landmarks", "clean")

    def __init__(self, mesh, up_row, pid, n_patch, base_lm, sparse: bool):
        W = mesh.num_workers
        self.comp = live_components(mesh, up_row)
        self.landmarks = None
        self.clean = None
        self.covered = None
        if not sparse:
            return
        # a dead existing link with both endpoints inside one patch makes
        # that patch dirty: its dimension-order prices may cross the gap
        nbr = mesh.neighbor_table
        dead = (nbr != topo.NO_NEIGHBOR) & ~np.asarray(up_row, bool)
        clean = np.ones(n_patch, bool)
        w_idx, d_idx = np.nonzero(dead)
        v_idx = nbr[w_idx, d_idx]
        in_patch = pid[w_idx] == pid[v_idx]
        clean[pid[w_idx[in_patch]]] = False
        self.clean = clean
        # landmarks: every patch center, plus the lowest-id worker of any
        # multi-worker component no center lands in (isolated sleepers are
        # singletons — `same_component` gates their flights, no landmark
        # needed). Component ids ARE lowest member ids, so the id doubles
        # as the representative.
        sizes = np.bincount(self.comp, minlength=W)
        multi = np.unique(self.comp[sizes[self.comp] > 1])
        covered = set(self.comp[base_lm].tolist())
        extras = np.asarray(sorted(set(multi.tolist()) - covered), np.int32)
        self.landmarks = np.concatenate([base_lm, extras]).astype(np.int32)
        self.covered = sizes[self.comp] > 1  # workers the bound must cover


def build_tables(schedule: LinkStateSchedule, mesh: topo.MeshTopology,
                 routing: str = "dense",
                 patch: tuple[int, int] | None = None
                 ) -> tuple[LinkStateArrays, RoutingBuildStats]:
    """Validate and compile a schedule for the simulator, with build stats.

    ``routing`` picks the outage-epoch pricing backend: "dense" builds one
    exact (W, W) live shortest-path table per distinct (τ, up) state;
    "sparse" builds O(W·L) landmark vectors with bounded stretch (module
    docstring); "auto" switches on mesh size (`resolve_routing`). `patch`
    overrides the sparse patch block shape (`topology.patch_dims` default).
    """
    t_begin = time.perf_counter()
    if mesh.num_workers != mesh.rows * mesh.cols:
        raise ValueError(
            "link-state simulation requires a fully populated grid "
            f"({mesh.rows}x{mesh.cols} vs {mesh.num_workers} workers)")
    schedule.validate(mesh)
    routing = resolve_routing(routing, mesh.num_workers)
    sparse = routing == "sparse"
    E = schedule.num_epochs
    W = mesh.num_workers
    R, C = mesh.rows, mesh.cols
    grid = np.arange(R * C).reshape(R, C)
    tau_v = schedule.link_tau[:, grid, SOUTH]                     # (E, R, C)
    tau_h = schedule.link_tau[:, grid, EAST]                      # (E, R, C)
    cum_v = np.concatenate([np.zeros((E, 1, C), np.int32),
                            np.cumsum(tau_v, axis=1, dtype=np.int32)], axis=1)
    cum_h = np.concatenate([np.zeros((E, R, 1), np.int32),
                            np.cumsum(tau_h, axis=2, dtype=np.int32)], axis=2)

    pid = n_patch = base_lm = None
    pr = pc = 0
    if sparse:
        pr, pc = patch if patch is not None else topo.patch_dims(mesh)
        pid, n_patch = topo.patch_ids(mesh, pr, pc)
        base_lm = np.unique(topo.patch_centers(mesh, pr, pc)).astype(np.int32)

    # route-around tables: one cost row per distinct outage link state
    # (dead EXISTING link somewhere); all-up epochs keep dimension-order
    # pricing and build nothing. Two-level dedup: structure on `up` alone,
    # costs on the full (τ, up) state.
    exists = mesh.neighbor_table != topo.NO_NEIGHBOR              # (W, 4)
    has_outage = (exists[None] & ~schedule.link_up).any(axis=(1, 2))  # (E,)
    detour_idx = np.full(E, -1, np.int32)
    comp = np.zeros((E, W), np.int32)
    structs: dict[bytes, _StructClass] = {}
    cost_classes: dict[bytes, int] = {}
    mats: list[np.ndarray] = []        # dense: (W, W); sparse: (L_s, W)
    cost_lms: list[np.ndarray] = []    # sparse: landmark ids per cost class
    cost_clean: list[np.ndarray] = []  # sparse: patch flags per cost class
    rhos: list[int] = []               # sparse: per-class coverage radius ρ
    struct_hits = cost_hits = 0
    for e in range(E):
        if not has_outage[e]:
            continue
        up_key = schedule.link_up[e].tobytes()
        sc = structs.get(up_key)
        if sc is None:
            sc = _StructClass(mesh, schedule.link_up[e], pid, n_patch,
                              base_lm, sparse)
            structs[up_key] = sc
        else:
            struct_hits += 1
        comp[e] = sc.comp
        cost_key = schedule.link_tau[e].tobytes() + up_key
        k = cost_classes.get(cost_key)
        if k is None:
            k = len(mats)
            cost_classes[cost_key] = k
            if sparse:
                d = landmark_costs(mesh, schedule.link_tau[e],
                                   schedule.link_up[e], sc.landmarks)
                mats.append(d)
                cost_lms.append(sc.landmarks)
                cost_clean.append(sc.clean)
                near = np.where(d < UNREACHABLE, d, np.int64(UNREACHABLE))
                cover = near.min(axis=0, initial=np.int64(UNREACHABLE))
                rhos.append(int(cover[sc.covered].max(initial=0)))
            else:
                mats.append(live_path_costs(mesh, schedule.link_tau[e],
                                            schedule.link_up[e]))
        else:
            cost_hits += 1
        detour_idx[e] = k

    detour = lm_cost = patch_clean_a = patch_id_a = None
    Lmax = 0
    if mats and not sparse:
        detour = jnp.asarray(np.stack(mats))
    elif mats:
        Lmax = max(m.shape[0] for m in mats)
        lm = np.full((len(mats), Lmax, W), _LM_INF, np.uint16)
        for k, m in enumerate(mats):
            finite = m < UNREACHABLE
            if (m[finite] >= int(_LM_INF)).any():
                raise ValueError(
                    "landmark cost exceeds the uint16 storage range — "
                    "link_tau values are implausibly large for this mesh")
            lm[k, :m.shape[0]] = np.where(finite, m, int(_LM_INF))
        lm_cost = jnp.asarray(lm)
        patch_clean_a = jnp.asarray(np.stack(cost_clean))
        patch_id_a = jnp.asarray(pid)
    arrays = LinkStateArrays(
        epoch_starts=jnp.asarray(schedule.epoch_starts, jnp.int32),
        link_tau=jnp.asarray(schedule.link_tau, jnp.int32),
        link_up=jnp.asarray(schedule.link_up),
        speed=jnp.asarray(schedule.speed, jnp.int32),
        cum_v=jnp.asarray(cum_v),
        cum_h=jnp.asarray(cum_h),
        detour=detour,
        detour_idx=jnp.asarray(detour_idx),
        comp=jnp.asarray(comp),
        lm_cost=lm_cost,
        patch_id=patch_id_a,
        patch_clean=patch_clean_a,
    )
    stats = RoutingBuildStats(
        routing=routing,
        num_epochs=E,
        outage_epochs=int(has_outage.sum()),
        struct_classes=len(structs),
        cost_classes=len(mats),
        struct_dedup_hits=struct_hits,
        cost_dedup_hits=cost_hits,
        table_bytes=table_bytes(arrays),
        dense_equiv_bytes=len(mats) * W * W * 4,
        build_seconds=time.perf_counter() - t_begin,
        num_landmarks=Lmax,
        num_patches=n_patch or 0,
        patch_shape=(pr, pc),
        stretch_add=2 * max(rhos, default=0),
    )
    return arrays, stats


def device_tables(schedule: LinkStateSchedule, mesh: topo.MeshTopology,
                  routing: str = "dense",
                  patch: tuple[int, int] | None = None) -> LinkStateArrays:
    """Validate and compile a schedule for the simulator (no stats)."""
    return build_tables(schedule, mesh, routing=routing, patch=patch)[0]


# --------------------------------------------------------------------------- #
# Traced helpers (usable inside lax.while_loop; E is small, O(E) scans are
# cheaper and more portable than searchsorted under old jax versions)
# --------------------------------------------------------------------------- #
def epoch_index(epoch_starts: jax.Array, t) -> jax.Array:
    """Index of the epoch containing tick `t` (t >= epoch_starts[0] == 0)."""
    return jnp.sum((epoch_starts <= t).astype(jnp.int32)) - 1


def _axis_cost(cum_ax, lo, hi, lane, n: int, torus_full: bool):
    """Path cost along one axis from index lo to hi in `lane`, picking the
    shorter ring arc (by hops, ties to the direct side) on a full torus."""
    direct = cum_ax[hi, lane] - cum_ax[lo, lane]
    if not torus_full:
        return direct
    ring = cum_ax[n, lane]
    d = hi - lo
    return jnp.where(n - d < d, ring - direct, direct)


def flight_ticks(tbl: LinkStateArrays, eidx, src, dst,
                 rows: int, cols: int, torus_full: bool) -> jax.Array:
    """Duration (ticks) of flights src[w] → dst[w] departing in epoch `eidx`.

    All-up epochs use dimension-order routing: vertical hops in the
    source's column, then horizontal hops in the destination's row, each
    hop priced at the active epoch's `link_tau` (reduces to `hops * tau`
    on a uniform schedule). Epochs with a dead link gather from that
    epoch's live-link shortest-path table instead, so flights are priced
    along real detours. Pairs the table marks unreachable fall back to the
    dimension-order cost — callers must gate flight *departures* on
    `same_component`, so the fallback is only consumed as the nominal-RTT
    timeout of a reply whose path was severed by an epoch flip mid-request.
    """
    W = rows * cols
    s = jnp.clip(src, 0, W - 1)
    d = jnp.clip(dst, 0, W - 1)
    rs, cs = s // cols, s % cols
    rd, cd = d // cols, d % cols
    cum_v = tbl.cum_v[eidx]                                     # (R+1, C)
    cum_h = tbl.cum_h[eidx]                                     # (R, C+1)
    vert = _axis_cost(cum_v, jnp.minimum(rs, rd), jnp.maximum(rs, rd),
                      cs, rows, torus_full)
    horz = _axis_cost(cum_h.T, jnp.minimum(cs, cd), jnp.maximum(cs, cd),
                      rd, cols, torus_full)
    base = (vert + horz).astype(jnp.int32)
    if not has_outage_tables(tbl):
        return base
    k = tbl.detour_idx[eidx]
    kc = jnp.maximum(k, 0)
    if tbl.detour is not None:
        det = tbl.detour[kc, s, d]                              # (W,) gather
        det = jnp.where(det < UNREACHABLE, det, base)
        return jnp.where(k >= 0, det, base)
    # sparse hierarchical pricing (module docstring): landmark triangle
    # costs everywhere, tightened to min(dimension-order, landmark) for
    # same-patch pairs in clean patches (where the dimension-order path is
    # a live in-patch path), 0 on the diagonal, and the dimension-order
    # timeout fallback for pairs the tables mark unreachable — identical
    # fallback semantics to the dense branch.
    lm = tbl.lm_cost[kc].astype(jnp.int32)                      # (L, W)
    lm = jnp.where(lm == jnp.int32(_LM_INF), UNREACHABLE, lm)
    cost = jnp.min(lm[:, s] + lm[:, d], axis=0)
    pid = tbl.patch_id
    exact = (pid[s] == pid[d]) & tbl.patch_clean[kc, pid[s]]
    cost = jnp.where(exact, jnp.minimum(base, cost), cost)
    cost = jnp.where(s == d, 0, cost)
    cost = jnp.where(cost < UNREACHABLE, cost, base)
    return jnp.where(k >= 0, cost, base)


def same_component(tbl: LinkStateArrays, eidx, a, b) -> jax.Array:
    """Per-worker: is there a live route between a[w] and b[w] in `eidx`?

    Component ids are per-epoch constants, so this is two O(1) gathers —
    the predicate behind "fully-partitioned workers are unreachable":
    the simulator refuses to launch a steal flight across components (and
    denies a grant whose reply path was severed mid-request).
    """
    if not has_outage_tables(tbl):
        return jnp.broadcast_to(
            jnp.bool_(True), jnp.broadcast_shapes(jnp.shape(a), jnp.shape(b)))
    c = tbl.comp[eidx]
    W = c.shape[0]
    return c[jnp.clip(a, 0, W - 1)] == c[jnp.clip(b, 0, W - 1)]
