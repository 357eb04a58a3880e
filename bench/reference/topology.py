"""2D mesh / torus topology for LEO constellations and TPU ICI meshes.

The paper (§2.1) models a LEO constellation as a 2D mesh: each satellite has
one optical ISL to the preceding/following satellite in its orbital plane and
one to the nearest satellite in each of the two adjacent planes — four links.
Some constellations add wrap-around (each plane is a ring), giving a torus.

This module is the single source of truth for worker coordinates, neighbor
tables, and hop distances. Everything is precomputed as static numpy/jnp
arrays at initialization (paper §3.1 step 1: "this set is precomputed at
initialization"); `repro.core.constellation` layers time-varying link state on
top for the dynamic-topology simulator.

Coordinates follow the paper's grid mapping (§4.1): workers 0..C-1 are placed
row-major on a ⌈√C⌉-wide grid; the last row may be partially filled, and
processes at the end of the last row have two neighbors, "the same as any
other corner process".
"""

from __future__ import annotations

import dataclasses
import math
from functools import cached_property

import jax.numpy as jnp
import numpy as np

# Direction encoding used across scheduler/simulator: N, S, W, E.
DIRECTIONS: tuple[tuple[int, int], ...] = ((-1, 0), (1, 0), (0, -1), (0, 1))
NUM_DIRECTIONS = len(DIRECTIONS)
NO_NEIGHBOR = -1

# Path cost of a worker pair with no live route between them. Small enough
# that sums with real link latencies never overflow int32, large enough that
# any comparison `cost < UNREACHABLE` cleanly separates routable pairs
# (real detours are bounded by W · max link τ, far below 2^28).
UNREACHABLE = np.int32(1 << 28)


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """A (possibly partial) 2D mesh of `num_workers` workers.

    rows, cols describe the bounding grid; workers fill it row-major, so the
    last row may be ragged (paper §4.1). `torus=True` adds wrap-around links
    (only meaningful when the grid is fully populated along that axis).
    """

    num_workers: int
    rows: int
    cols: int
    torus: bool = False

    def __post_init__(self):
        if self.num_workers < 1:
            raise ValueError("num_workers must be >= 1")
        if self.rows * self.cols < self.num_workers:
            raise ValueError(
                f"grid {self.rows}x{self.cols} too small for {self.num_workers} workers"
            )

    # ------------------------------------------------------------------ #
    # Construction
    # ------------------------------------------------------------------ #
    @staticmethod
    def square(num_workers: int, torus: bool = False) -> "MeshTopology":
        """Paper §4.1 mapping: side length ⌈√C⌉, rows filled in order."""
        side = math.isqrt(num_workers)
        if side * side < num_workers:
            side += 1
        rows = (num_workers + side - 1) // side
        return MeshTopology(num_workers=num_workers, rows=rows, cols=side, torus=torus)

    @staticmethod
    def grid(rows: int, cols: int, torus: bool = False) -> "MeshTopology":
        return MeshTopology(num_workers=rows * cols, rows=rows, cols=cols, torus=torus)

    # ------------------------------------------------------------------ #
    # Coordinates
    # ------------------------------------------------------------------ #
    def coords_of(self, worker: int) -> tuple[int, int]:
        return divmod(worker, self.cols)

    def worker_at(self, r: int, c: int) -> int:
        w = r * self.cols + c
        return w if (0 <= r < self.rows and 0 <= c < self.cols and w < self.num_workers) else NO_NEIGHBOR

    @cached_property
    def coords(self) -> np.ndarray:
        """(num_workers, 2) int32 array of (row, col)."""
        ws = np.arange(self.num_workers)
        return np.stack([ws // self.cols, ws % self.cols], axis=1).astype(np.int32)

    # ------------------------------------------------------------------ #
    # Neighbor tables
    # ------------------------------------------------------------------ #
    @cached_property
    def neighbor_table(self) -> np.ndarray:
        """(num_workers, 4) int32: neighbor id per direction or NO_NEIGHBOR.

        Directions follow `DIRECTIONS` (N, S, W, E). With `torus=True`, edges
        wrap when the corresponding axis is fully populated.
        """
        tab = np.full((self.num_workers, NUM_DIRECTIONS), NO_NEIGHBOR, dtype=np.int32)
        full_rows = self.num_workers // self.cols  # rows that are completely filled
        for w in range(self.num_workers):
            r, c = divmod(w, self.cols)
            for d, (dr, dc) in enumerate(DIRECTIONS):
                rr, cc = r + dr, c + dc
                if self.torus:
                    # Wrap columns only inside fully-populated rows; wrap rows
                    # only when the column exists in the last row too.
                    if dc != 0 and r < full_rows:
                        cc %= self.cols
                    if dr != 0:
                        col_height = self.rows if (self.worker_at(self.rows - 1, c) != NO_NEIGHBOR) else self.rows - 1
                        rr %= col_height
                nb = self.worker_at(rr, cc)
                tab[w, d] = nb
        return tab

    @cached_property
    def neighbor_counts(self) -> np.ndarray:
        return (self.neighbor_table != NO_NEIGHBOR).sum(axis=1).astype(np.int32)

    def neighbors_of(self, worker: int) -> list[int]:
        return [int(n) for n in self.neighbor_table[worker] if n != NO_NEIGHBOR]

    # ------------------------------------------------------------------ #
    # Hop distances (paper §3.3 assumption ii: shortest paths)
    # ------------------------------------------------------------------ #
    def hops(self, a: int, b: int) -> int:
        ra, ca = self.coords_of(a)
        rb, cb = self.coords_of(b)
        dr = abs(ra - rb)
        dc = abs(ca - cb)
        if self.torus:
            full_rows = self.num_workers // self.cols
            if full_rows == self.rows:  # only exact tori wrap cleanly
                dr = min(dr, self.rows - dr)
                dc = min(dc, self.cols - dc)
        return dr + dc

    @cached_property
    def hop_matrix(self) -> np.ndarray:
        """(num_workers, num_workers) int32 Manhattan hop distances."""
        rc = self.coords  # (W, 2)
        dr = np.abs(rc[:, None, 0] - rc[None, :, 0])
        dc = np.abs(rc[:, None, 1] - rc[None, :, 1])
        if self.torus and self.num_workers == self.rows * self.cols:
            dr = np.minimum(dr, self.rows - dr)
            dc = np.minimum(dc, self.cols - dc)
        return (dr + dc).astype(np.int32)

    def mean_hops(self) -> float:
        """Average hop count between two distinct uniform-random workers.

        For a full √N×√N mesh this approaches the paper's (2/3)·√N.
        """
        h = self.hop_matrix
        n = self.num_workers
        if n == 1:
            return 0.0
        return float(h.sum() / (n * (n - 1)))

    def torus_full(self) -> bool:
        """Whether the hop metric wraps (exact torus: every grid slot filled)."""
        return self.torus and self.num_workers == self.rows * self.cols

    # ------------------------------------------------------------------ #
    # JAX-side views
    # ------------------------------------------------------------------ #
    def neighbor_table_jnp(self) -> jnp.ndarray:
        return jnp.asarray(self.neighbor_table)

    def ppermute_pairs(self, direction: int) -> list[tuple[int, int]]:
        """Static (src, dst) pairs for `jax.lax.ppermute` along one direction.

        Sends from each worker to its `direction`-neighbor; workers without a
        neighbor in that direction do not send (their slot receives zeros on
        the other end per ppermute semantics).
        """
        pairs = []
        for w in range(self.num_workers):
            nb = int(self.neighbor_table[w, direction])
            if nb != NO_NEIGHBOR:
                pairs.append((w, nb))
        return pairs


def hop_dist(mesh: MeshTopology, coords, victim):
    """Per-worker Manhattan hop count to ``victim[w]`` (torus-aware).

    `coords` is the (W, 2) device-side coordinate table; entries of `victim`
    are clipped, so NO_NEIGHBOR lanes return a garbage-but-in-range distance
    the caller is expected to mask. Equivalent to gathering from the dense
    pairwise distance table without ever materializing it — O(W) gathers, so
    W >= 4k meshes never embed multi-MB constants in the compiled graph.
    """
    v = jnp.clip(victim, 0, mesh.num_workers - 1)
    dr = jnp.abs(coords[:, 0] - coords[v, 0])
    dc = jnp.abs(coords[:, 1] - coords[v, 1])
    if mesh.torus_full():
        dr = jnp.minimum(dr, mesh.rows - dr)
        dc = jnp.minimum(dc, mesh.cols - dc)
    return (dr + dc).astype(jnp.int32)


# ------------------------------------------------------------------------- #
# Patch partition + landmarks (sparse hierarchical routing support)
# ------------------------------------------------------------------------- #
# Default edge length of a routing patch: rectangular blocks of the grid
# inside which dimension-order pricing is kept exact by the sparse routing
# backend (see linkstate module docstring). An axis shorter than twice the
# target collapses to the full axis — then every ring arc stays inside the
# patch; otherwise the block is a strict sub-range and must span at most
# half the axis so the shorter ring arc of any same-patch pair is always
# the direct (in-patch) one. `patch_dims` maintains that invariant.
PATCH_TARGET = 32


def patch_dims(mesh: MeshTopology, target: int = PATCH_TARGET) -> tuple[int, int]:
    """(patch_rows, patch_cols) block shape for hierarchical routing."""
    if target < 1:
        raise ValueError("patch target must be >= 1")

    def pick(n: int) -> int:
        # strict sub-blocks must satisfy block - 1 <= n // 2 so a full
        # torus's shorter arc between same-patch coordinates never wraps;
        # guaranteed by collapsing short axes to the full axis.
        return n if n < 2 * target else target

    return pick(mesh.rows), pick(mesh.cols)


def patch_ids(mesh: MeshTopology, pr: int, pc: int) -> tuple[np.ndarray, int]:
    """((W,) int32 patch index per worker, number of patches).

    Patches tile the grid row-major in (pr, pc) blocks (trailing blocks may
    be ragged). Requires a fully populated grid, like every link-state
    consumer.
    """
    if not (1 <= pr <= mesh.rows and 1 <= pc <= mesh.cols):
        raise ValueError(f"patch dims ({pr}, {pc}) outside grid "
                         f"{mesh.rows}x{mesh.cols}")
    npc = -(-mesh.cols // pc)
    r, c = mesh.coords[:, 0], mesh.coords[:, 1]
    pid = ((r // pr) * npc + (c // pc)).astype(np.int32)
    npr = -(-mesh.rows // pr)
    return pid, int(npr * npc)


def patch_centers(mesh: MeshTopology, pr: int, pc: int) -> np.ndarray:
    """(P,) int32 worker id at the center of each patch block, in patch-id
    order — the sparse routing backend's base landmark set (one per patch)."""
    npr = -(-mesh.rows // pr)
    npc = -(-mesh.cols // pc)
    out = np.empty(npr * npc, np.int32)
    for i in range(npr):
        r0, r1 = i * pr, min((i + 1) * pr, mesh.rows)
        rc = (r0 + r1 - 1) // 2
        for j in range(npc):
            c0, c1 = j * pc, min((j + 1) * pc, mesh.cols)
            cc = (c0 + c1 - 1) // 2
            out[i * npc + j] = rc * mesh.cols + cc
    return out


def detour_matrix(mesh: MeshTopology, link_tau: np.ndarray,
                  link_up: np.ndarray) -> np.ndarray:
    """(W, W) all-pairs shortest-path costs over LIVE links — test oracle.

    Dense Floyd–Warshall, O(W^3) and host-side only: the reference that
    `linkstate.live_path_costs` (the vectorized repeated-min-plus computation
    used to compile route-around tables) is asserted against in tests.
    `link_tau`/`link_up` are (W, 4) rows in `DIRECTIONS` order; dead or
    non-existent links contribute no edge. Pairs with no live route are
    pinned at `UNREACHABLE`. With all links up and uniform τ this equals
    ``hop_matrix * τ`` (dimension-order routing cost).
    """
    W = mesh.num_workers
    inf = np.int64(1) << 40
    d = np.full((W, W), inf, np.int64)
    np.fill_diagonal(d, 0)
    nbr = mesh.neighbor_table
    for w in range(W):
        for k in range(NUM_DIRECTIONS):
            v = int(nbr[w, k])
            if v != NO_NEIGHBOR and bool(link_up[w, k]):
                d[w, v] = min(d[w, v], int(link_tau[w, k]))
    for k in range(W):
        d = np.minimum(d, d[:, k : k + 1] + d[k : k + 1, :])
    return np.minimum(d, UNREACHABLE).astype(np.int32)


