"""Fixed-capacity work-stealing deques, vectorized over workers, in pure JAX.

Semantics follow Itoyori/ItoyoriFBC (paper §2.2):

  * the owner pushes and pops at the **top** (LIFO — depth-first execution of
    freshly spawned tasks);
  * thieves steal from the **bottom** (FIFO end — the oldest, typically
    largest-grained task).

JAX needs static shapes, so each worker's deque is a ring buffer of capacity
`C` holding fixed-width int32 task records. The whole constellation's deques
are one `(W, C, T)` array plus `(W,)` bottom indices and sizes; every
operation below is batched across all workers and usable inside
`jax.lax.while_loop` / `shard_map`.

All operations are functional and masked: `mask[w] == False` leaves worker
`w`'s deque untouched. Overflow never corrupts the buffer — pushes that would
overflow are dropped and reported via a flag the caller must check (the
schedulers surface it in their stats, tests assert it stays zero).

"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

TASK_WIDTH = 4  # [kind, a, b, c] int32 record


class DequeState(NamedTuple):
    buf: jax.Array   # (W, C, T) int32 ring buffers
    bot: jax.Array   # (W,) int32 index of bottom element
    size: jax.Array  # (W,) int32 number of live tasks


def make(num_workers: int, capacity: int, width: int = TASK_WIDTH) -> DequeState:
    return DequeState(
        buf=jnp.zeros((num_workers, capacity, width), dtype=jnp.int32),
        bot=jnp.zeros((num_workers,), dtype=jnp.int32),
        size=jnp.zeros((num_workers,), dtype=jnp.int32),
    )


def capacity(state: DequeState) -> int:
    return state.buf.shape[1]


def _warange(state: DequeState) -> jax.Array:
    return jnp.arange(state.buf.shape[0])


def push_top(state: DequeState, task: jax.Array, mask: jax.Array):
    """Push `task[w]` onto worker w's top where `mask[w]`.

    Returns (state, ok) — ok[w] False when the deque was full (push dropped).
    """
    cap = capacity(state)
    ok = mask & (state.size < cap)
    idx = (state.bot + state.size) % cap
    w = _warange(state)
    # Write unconditionally at idx, then select: rows with ok=False keep old row.
    new_buf = state.buf.at[w, idx].set(
        jnp.where(ok[:, None], task, state.buf[w, idx])
    )
    new_size = state.size + ok.astype(jnp.int32)
    return DequeState(new_buf, state.bot, new_size), ok


def push_top_many(state: DequeState, tasks: jax.Array, counts: jax.Array):
    """Push `tasks[w, :counts[w]]` (K-slot staging buffer) onto worker w's top.

    K is a static small constant (max children per expansion). Returns
    (state, overflowed) where overflowed[w] counts dropped tasks.
    """
    W, k_max = tasks.shape[:2]
    cap = capacity(state)
    room = cap - state.size
    pushed = jnp.minimum(counts, room)
    overflow = counts - pushed

    # one batched scatter for all K slots (the K-step unroll this replaces
    # paid one full (W, C, T) materialization per slot). Dropped lanes are
    # routed out of bounds — XLA scatter skips them — instead of issuing
    # no-op read-modify-writes whose duplicate-index order is undefined.
    ranks = jnp.arange(k_max)[None, :]                       # (1, K)
    idx = (state.bot[:, None] + state.size[:, None] + ranks) % cap
    live = ranks < pushed[:, None]
    dst_w = jnp.where(live, _warange(state)[:, None], W)
    buf = state.buf.at[dst_w, idx].set(tasks, mode="drop")
    return DequeState(buf, state.bot, state.size + pushed), overflow


def pop_top(state: DequeState, mask: jax.Array):
    """Pop worker w's top task where `mask[w]` and size > 0.

    Returns (state, task, ok). `task[w]` is garbage when not ok[w].
    """
    cap = capacity(state)
    ok = mask & (state.size > 0)
    new_size = state.size - ok.astype(jnp.int32)
    idx = (state.bot + new_size) % cap
    task = state.buf[_warange(state), idx]
    return DequeState(state.buf, state.bot, new_size), task, ok


def peek_bottom(state: DequeState, rank: jax.Array) -> jax.Array:
    """Read the task `rank` positions above worker w's bottom (no removal)."""
    cap = capacity(state)
    idx = (state.bot + rank) % cap
    return state.buf[_warange(state), idx]


def peek_bottom_window(state: DequeState, window: int) -> jax.Array:
    """(W, window, T) view of each worker's bottom `window` slots (cyclic).

    Entries beyond `size` are garbage; callers mask with `state.size`.
    """
    cap = capacity(state)
    ranks = jnp.arange(window)[None, :]  # (1, window)
    idx = (state.bot[:, None] + ranks) % cap  # (W, window)
    return jnp.take_along_axis(state.buf, idx[:, :, None], axis=1)


def export_bottom(state: DequeState, grants: jax.Array, width: int):
    """Extract `grants[w]` bottom records into a dense staging block and
    advance each deque's bottom — the victim side of a steal round.

    Returns (stolen, state): `stolen` is (W, width, T) with the first
    min(grants, size)[w] rows of worker w's bottom window and zeros beyond;
    thief t reads `stolen[victim[t], rank[t]]`.
    """
    # never advance the bottom past what the staging block exports: a
    # grant beyond `width` would hand thieves duplicate records while the
    # victim silently loses the real tasks
    grants = jnp.minimum(grants, width)
    g = jnp.minimum(grants, state.size)
    ranks = jnp.arange(width)[None, :]
    rows = peek_bottom_window(state, width)
    stolen = jnp.where((ranks < g[:, None])[:, :, None], rows, 0)
    return stolen, steal_bottom(state, g)


def steal_bottom(state: DequeState, counts: jax.Array) -> DequeState:
    """Remove `counts[w]` tasks from worker w's bottom (already handed out).

    Callers must have gathered the stolen records with `peek_bottom*` first
    and must guarantee counts <= size.
    """
    cap = capacity(state)
    taken = jnp.minimum(counts, state.size)
    return DequeState(state.buf, (state.bot + taken) % cap, state.size - taken)


