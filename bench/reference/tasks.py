"""Task-tree workloads for the work-stealing executors (paper §4.1).

Two benchmarks, matching the paper:

  * **FIB** — recursive Fibonacci as a nested fork-join tree (Listing 1.1).
    We use the *leaf-sum* formulation: fib(n) equals the sum of fib(k) over
    the leaves (k < 2 or k <= cutoff) of the recursion tree, so no futures /
    result write-backs are needed — results combine by commutative addition,
    which matches how ItoyoriFBC's side-effect variant accumulates. Subtrees
    with n <= cutoff are "computed sequentially": the worker is busy for
    `seq_cost(n)` work units and adds fib(n) to its accumulator. The paper
    uses n=62, cutoff=32 on 640 cores; our CPU-scale defaults shrink n but
    keep the balanced-tree structure.

  * **UTS** — Unbalanced Tree Search, geometric variant (Olivier et al.):
    each node's child count is drawn from a geometric distribution whose mean
    decays linearly from b0 at the root to 0 at depth d_max (UTS's "linear"
    shape), sampled from a splittable integer hash of (seed, child index).
    Severe imbalance comes from the tree shape; every node costs one work
    unit. Paper parameters: b0=4, d=16, r=19 (≈1e9 nodes — HPC scale); our
    defaults shrink d. Child counts are capped at CHILD_CAP (P(overflow)
    < 1e-6 at b0=4) and emitted in chunks of EXPAND_K-1 per expansion so a
    single deque push stays fixed-width.

Task records are `[kind, a, b, c]` int32:
    FIB   : [1, n,      0,     0]
    UTS   : [2, depth,  seed,  0]
    CHUNK : [3, depth,  seed,  start*256 + count]   (continuation of UTS expand)
    REQ   : [4, cost,   inject_tick, task_id]       (open-loop user request —
            see `core/arrivals.py`; a leaf costing `cost` work units whose
            inject tick rides in the record so the sojourn ledger can price
            queue wait at pop time)

Expansion is a pure function `(task, table) -> (children, n_children,
leaf_value, leaf_cost, is_node)` vectorized over workers; both the
round-based scheduler and the latency simulator share it.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

KIND_NONE = 0
KIND_FIB = 1
KIND_UTS = 2
KIND_CHUNK = 3
KIND_REQ = 4

EXPAND_K = 8          # staging slots per expansion (children + continuation)
CHILD_CAP = 64        # max children of a UTS node (geometric tail cut)
RESULT_MOD = np.int64(2**31 - 1)  # accumulators are checksums mod a Mersenne prime


# --------------------------------------------------------------------------- #
# Integer hashing (splittable, uint32, wraps naturally in jnp)
# --------------------------------------------------------------------------- #
def _hash2(x, y):
    """Mix two uint32 streams into one well-scrambled uint32 (lowbias32-style)."""
    x = x.astype(jnp.uint32)
    y = y.astype(jnp.uint32)
    h = x * jnp.uint32(0x9E3779B9) + y * jnp.uint32(0x85EBCA6B) + jnp.uint32(0x27220A95)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


def child_seed(seed, index):
    """Seed of the `index`-th child of a node with `seed` (int32-safe)."""
    h = _hash2(seed.astype(jnp.uint32), index.astype(jnp.uint32))
    return (h >> 1).astype(jnp.int32)  # keep non-negative in int32


# --------------------------------------------------------------------------- #
# Workload tables (host-side precompute; static under jit)
# --------------------------------------------------------------------------- #
@lru_cache(maxsize=None)
def fib_mod_table(n_max: int = 94) -> np.ndarray:
    t = np.zeros(n_max + 1, dtype=np.int64)
    t[1] = 1
    for i in range(2, n_max + 1):
        t[i] = (t[i - 1] + t[i - 2]) % RESULT_MOD
    return t.astype(np.int32)


@lru_cache(maxsize=None)
def fib_seq_nodes(n_max: int = 94) -> np.ndarray:
    """Nodes in the naive fib recursion tree: s(n) = 1 + s(n-1) + s(n-2)."""
    t = np.ones(n_max + 1, dtype=np.float64)
    for i in range(2, n_max + 1):
        t[i] = 1.0 + t[i - 1] + t[i - 2]
    return t


@dataclasses.dataclass(frozen=True)
class FibWorkload:
    """FIB(n) with sequential cutoff. Leaf cost ∝ naive subtree size, scaled
    into `max_leaf_cost` work units so CPU-scale runs stay tractable while the
    balanced-tree *structure* (and the cutoff-induced cost spread) match the
    paper's setup."""

    n: int = 34
    cutoff: int = 18
    max_leaf_cost: int = 64

    def __post_init__(self):
        if not (2 <= self.cutoff <= self.n <= 94):
            raise ValueError("require 2 <= cutoff <= n <= 94")

    def root_task(self) -> np.ndarray:
        return np.array([KIND_FIB, self.n, 0, 0], dtype=np.int32)

    def tables(self):
        costs = fib_seq_nodes()[: self.cutoff + 1]
        scale = self.max_leaf_cost / max(costs.max(), 1.0)
        cost_tab = np.maximum(1, np.round(costs * scale)).astype(np.int32)
        cost_full = np.zeros(95, dtype=np.int32)
        cost_full[: self.cutoff + 1] = cost_tab
        return {
            "fib_mod": jnp.asarray(fib_mod_table()),
            "fib_cost": jnp.asarray(cost_full),
            "fib_cutoff": jnp.int32(self.cutoff),
            "uts_logq": jnp.float32(0.0),
            "uts_b0": jnp.float32(0.0),
            "uts_dmax": jnp.int32(0),
        }

    # ---- host-side oracles for tests ------------------------------------ #
    def expected_result(self) -> int:
        return int(fib_mod_table()[self.n])

    def expected_nodes(self) -> int:
        @lru_cache(maxsize=None)
        def nodes(n):
            return 1 if n <= self.cutoff else 1 + nodes(n - 1) + nodes(n - 2)
        return nodes(self.n)

    def expected_work_units(self) -> int:
        cost = fib_seq_nodes()
        scale = self.max_leaf_cost / max(cost[: self.cutoff + 1].max(), 1.0)
        cost_tab = np.maximum(1, np.round(cost * scale)).astype(np.int64)

        @lru_cache(maxsize=None)
        def work(n):
            if n <= self.cutoff:
                return int(cost_tab[n])
            return 1 + work(n - 1) + work(n - 2)
        return work(self.n)


@dataclasses.dataclass(frozen=True)
class UtsWorkload:
    """UTS geometric tree, linear branching decay b(d) = b0·(1 − d/d_max).

    The child count of a node at depth d with hash-uniform u ∈ (0,1] is
    floor(log u / log q_d) with q_d = b(d)/(1 + b(d)) (geometric with mean
    b(d)), capped at CHILD_CAP; the i-th child's seed is
    `child_seed(seed, i)`. Every node costs one work unit and adds 1 to the
    result checksum."""

    b0: float = 4.0
    d_max: int = 10
    root_seed: int = 19

    def root_task(self) -> np.ndarray:
        return np.array([KIND_UTS, 0, self.root_seed, 0], dtype=np.int32)

    def tables(self):
        return {
            "fib_mod": jnp.asarray(fib_mod_table()),
            "fib_cost": jnp.ones(95, dtype=jnp.int32),
            "fib_cutoff": jnp.int32(0),
            "uts_b0": jnp.float32(self.b0),
            "uts_dmax": jnp.int32(self.d_max),
            "uts_logq": jnp.float32(0.0),
        }

    # ---- closed form: the tree enumerated level by level on the host ---- #
    def expected_nodes(self, max_nodes: int = 50_000_000) -> int:
        """Exact node count: a breadth-first walk on the host whose child
        counts and seeds come from `_uts_child_count` and `child_seed`,
        evaluated on JAX's default device (the platform's float32 `log`
        and divide decide boundary children). Each level is padded to a
        power of two with depth `d_max` rows, which have no children, so
        the walk compiles once per padded size."""
        depths = np.zeros(1, np.int32)
        seeds = np.asarray([self.root_seed], np.int32)
        b0, d_max = jnp.float32(self.b0), jnp.int32(self.d_max)
        n = 0
        while seeds.size:
            n += seeds.size
            if n > max_nodes:
                raise RuntimeError(f"UTS tree larger than {max_nodes} nodes")
            pad = _bucket(seeds.size) - seeds.size
            ms = np.asarray(_counts_jit(
                jnp.asarray(np.pad(depths, (0, pad),
                                   constant_values=self.d_max)),
                jnp.asarray(np.pad(seeds, (0, pad))), b0, d_max))[:seeds.size]
            total = int(ms.sum())
            if total == 0:
                break
            parent = np.repeat(np.arange(seeds.size), ms)
            child_ix = np.arange(total) - np.repeat(np.cumsum(ms) - ms, ms)
            pad = _bucket(total) - total
            seeds = np.asarray(_child_seed_jit(
                jnp.asarray(np.pad(seeds[parent], (0, pad))),
                jnp.asarray(np.pad(child_ix.astype(np.int32), (0, pad)))
            ))[:total]
            depths = depths[parent] + 1
        return n

    def expected_result(self) -> int:
        """Every node adds 1 to the checksum mod RESULT_MOD."""
        return self.expected_nodes() % int(RESULT_MOD)


def _bucket(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


# --------------------------------------------------------------------------- #
# In-graph expansion (vectorized over workers)
# --------------------------------------------------------------------------- #
def _uts_child_count(depth, seed, b0, d_max):
    """Vectorized geometric child count with linear decay (see UtsWorkload)."""
    h = _hash2(seed.astype(jnp.uint32), jnp.uint32(0xFFFF))
    u = (h.astype(jnp.float32) + 1.0) * jnp.float32(2.0**-32)
    frac = 1.0 - depth.astype(jnp.float32) / jnp.maximum(d_max.astype(jnp.float32), 1.0)
    b_d = b0 * frac
    q = b_d / (1.0 + b_d)
    safe_q = jnp.clip(q, 1e-9, 1.0 - 1e-9)
    m = jnp.floor(jnp.log(jnp.maximum(u, 1e-38)) / jnp.log(safe_q)).astype(jnp.int32)
    m = jnp.clip(m, 0, CHILD_CAP)
    return jnp.where((depth >= d_max) | (b_d <= 0.0), 0, m)


_counts_jit = jax.jit(_uts_child_count)
_child_seed_jit = jax.jit(child_seed)


def expand(task, active, tables):
    """Expand one task per worker.

    Args:
      task: (W, 4) int32 records.
      active: (W,) bool — workers actually expanding this step.
      tables: workload tables from `*Workload.tables()`.

    Returns dict with:
      children:   (W, EXPAND_K, 4) staged child records
      n_children: (W,) int32
      value:      (W,) int32 contribution to the result accumulator
      cost:       (W,) int32 work units the worker is busy after this expansion
      nodes:      (W,) int32 1 if this expansion consumed a real tree node
    """
    kind = task[:, 0]
    a, b, c = task[:, 1], task[:, 2], task[:, 3]
    W = task.shape[0]
    zeros_children = jnp.zeros((W, EXPAND_K, 4), dtype=jnp.int32)

    # ---------------- FIB ------------------------------------------------- #
    is_fib = active & (kind == KIND_FIB)
    n = jnp.clip(a, 0, 94)
    fib_leaf = n <= tables["fib_cutoff"]
    fib_children = zeros_children
    fib_children = fib_children.at[:, 0, :].set(
        jnp.stack([jnp.full((W,), KIND_FIB, jnp.int32), n - 1,
                   jnp.zeros((W,), jnp.int32), jnp.zeros((W,), jnp.int32)], axis=1))
    fib_children = fib_children.at[:, 1, :].set(
        jnp.stack([jnp.full((W,), KIND_FIB, jnp.int32), n - 2,
                   jnp.zeros((W,), jnp.int32), jnp.zeros((W,), jnp.int32)], axis=1))
    fib_n_children = jnp.where(fib_leaf, 0, 2)
    fib_value = jnp.where(fib_leaf, tables["fib_mod"][n], 0)
    fib_cost = jnp.where(fib_leaf, tables["fib_cost"][n], 1)

    # ---------------- UTS node -------------------------------------------- #
    is_uts = active & (kind == KIND_UTS)
    m = _uts_child_count(a, b, tables["uts_b0"], tables["uts_dmax"])
    # ---------------- UTS chunk continuation ------------------------------ #
    is_chunk = active & (kind == KIND_CHUNK)
    ch_start = c // 256
    ch_count = c % 256
    # Unified: a UTS node is a chunk with start=0, count=m.
    start = jnp.where(is_chunk, ch_start, 0)
    count = jnp.where(is_chunk, ch_count, m)

    emit = jnp.minimum(count, EXPAND_K - 1)
    uts_children = zeros_children
    for i in range(EXPAND_K - 1):  # static unroll
        idx = start + i
        rec = jnp.stack(
            [jnp.full((W,), KIND_UTS, jnp.int32), a + 1, child_seed(b, idx),
             jnp.zeros((W,), jnp.int32)], axis=1)
        uts_children = uts_children.at[:, i, :].set(rec)
    rem = count - emit
    cont = jnp.stack(
        [jnp.full((W,), KIND_CHUNK, jnp.int32), a, b, (start + emit) * 256 + rem], axis=1)
    has_cont = rem > 0
    k_slot = emit  # continuation goes right after the emitted children
    uts_children = uts_children.at[jnp.arange(W), k_slot, :].set(
        jnp.where(has_cont[:, None], cont, uts_children[jnp.arange(W), k_slot]))
    uts_n_children = emit + has_cont.astype(jnp.int32)
    uts_value = jnp.where(is_uts, 1, 0)  # count nodes; chunks are bookkeeping
    uts_cost = jnp.ones((W,), jnp.int32)

    # ---------------- REQ leaf (open-loop arrival) ------------------------- #
    # No children; the worker burns the injected `cost` and contributes the
    # task_id to the result checksum (so leap ≡ tick covers request work).
    is_req = active & (kind == KIND_REQ)

    # ---------------- combine --------------------------------------------- #
    sel_fib = is_fib[:, None, None]
    children = jnp.where(sel_fib, fib_children, uts_children)
    n_children = jnp.where(is_fib, fib_n_children,
                           jnp.where(is_uts | is_chunk, uts_n_children, 0))
    value = jnp.where(is_fib, fib_value,
                      jnp.where(is_uts, uts_value, jnp.where(is_req, c, 0)))
    cost = jnp.where(is_fib, fib_cost,
                     jnp.where(is_uts | is_chunk, uts_cost,
                               jnp.where(is_req, jnp.maximum(a, 1), 0)))
    nodes = (is_fib | is_uts | is_req).astype(jnp.int32)
    n_children = jnp.where(active, n_children, 0)
    value = jnp.where(active, value, 0)
    cost = jnp.where(active, cost, 0)
    nodes = jnp.where(active, nodes, 0)
    return {"children": children, "n_children": n_children, "value": value,
            "cost": cost, "nodes": nodes}
