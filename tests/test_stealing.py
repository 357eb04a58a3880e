"""Steal-conflict resolution: sorted segment ranking ≡ pairwise reference."""

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st  # degrades to skips without hypothesis

from repro.core import stealing, topology

FIELDS = ("victim", "rank", "got", "taken", "hops")


# --------------------------------------------------------------------------- #
# radius2_list: vectorized offset enumeration ≡ hop-matrix scan
# --------------------------------------------------------------------------- #
def _radius2_reference(mesh):
    """The pre-vectorization implementation: row-by-row hop-matrix scan."""
    W = mesh.num_workers
    h = mesh.hop_matrix
    out = np.full((W, 12), topology.NO_NEIGHBOR, dtype=np.int32)
    for w in range(W):
        cand = np.where((h[w] > 0) & (h[w] <= 2))[0]
        out[w, : len(cand)] = cand[:12]
    return out


@pytest.mark.parametrize("mesh", [
    topology.MeshTopology.square(16),
    topology.MeshTopology.square(10),              # ragged last row
    topology.MeshTopology.grid(4, 5, torus=True),  # full torus
    topology.MeshTopology.grid(2, 3, torus=True),  # tiny torus: offset aliasing
    topology.MeshTopology.grid(3, 3, torus=True),
    topology.MeshTopology.grid(1, 6),
    topology.MeshTopology.square(1),
], ids=lambda m: f"{m.rows}x{m.cols}{'t' if m.torus else ''}w{m.num_workers}")
def test_radius2_vectorized_matches_hop_matrix_scan(mesh):
    np.testing.assert_array_equal(stealing.radius2_list(mesh),
                                  _radius2_reference(mesh))


def test_choose_adaptive_linkaware_prefers_cheapest_live():
    """With distinct link costs the near pick is the τ-argmin live neighbor;
    dead links are excluded; all-dead rows return NO_NEIGHBOR."""
    import jax
    mesh = topology.MeshTopology.square(9)
    nbrs = jnp.asarray(stealing.neighbor_list(mesh))
    W = mesh.num_workers
    tau = jnp.asarray(np.arange(4)[None, :] + 2 + np.zeros((W, 1)),
                      jnp.int32)  # direction d costs 2+d, unique per row
    up = jnp.asarray(np.ones((W, 4), bool))
    masked = jnp.where(up & (nbrs >= 0), nbrs, topology.NO_NEIGHBOR)
    is_thief = jnp.ones((W,), bool)
    fails = jnp.zeros((W,), jnp.int32)
    r2 = jnp.asarray(stealing.radius2_list(mesh))
    v = stealing.choose_adaptive_linkaware(jax.random.PRNGKey(0), masked, r2,
                                           tau, fails, is_thief)
    # the cheapest existing direction per worker is the lowest direction index
    first_dir = np.argmax(np.asarray(nbrs) >= 0, axis=1)
    np.testing.assert_array_equal(
        np.asarray(v), np.asarray(nbrs)[np.arange(W), first_dir])
    # all links dead -> no victim (the simulator's leap relies on this)
    dead = jnp.full((W, 4), topology.NO_NEIGHBOR, jnp.int32)
    v2 = stealing.choose_adaptive_linkaware(jax.random.PRNGKey(0), dead, r2,
                                            tau, fails, is_thief)
    assert (np.asarray(v2) == topology.NO_NEIGHBOR).all()


def _random_instance(rng, W):
    victim = rng.integers(-1, W, W).astype(np.int32)
    victim = np.where(victim == np.arange(W), -1, victim)  # no self-steals
    sizes = rng.integers(0, 8, W).astype(np.int32)
    priority = (rng.integers(0, 5, W).astype(np.int32)
                if rng.random() < 0.5 else None)
    budget = int(rng.integers(1, stealing.GRANT_WIDTH + 1))
    return victim, sizes, priority, budget


def _assert_plans_equal(victim, sizes, budget, priority):
    pri = None if priority is None else jnp.asarray(priority)
    a = stealing.resolve_grants(jnp.asarray(victim), jnp.asarray(sizes),
                                budget, pri)
    b = stealing.resolve_grants_pairwise(jnp.asarray(victim),
                                         jnp.asarray(sizes), budget, pri)
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"sorted vs pairwise mismatch in {f}")


@pytest.mark.parametrize("seed", range(8))
def test_resolve_grants_sorted_equals_pairwise_random(seed):
    """Property: the O(W log W) sort-based resolution is bit-identical to
    the O(W^2) pairwise reference over random victim/priority/size vectors
    (seeded sweep — runs with or without hypothesis)."""
    rng = np.random.default_rng(seed)
    for _ in range(25):
        W = int(rng.integers(1, 50))
        victim, sizes, priority, budget = _random_instance(rng, W)
        _assert_plans_equal(victim, sizes, budget, priority)


@given(st.integers(0, 2**32 - 1), st.integers(1, 64))
@settings(max_examples=50, deadline=None)
def test_resolve_grants_sorted_equals_pairwise_hypothesis(seed, W):
    victim, sizes, priority, budget = _random_instance(
        np.random.default_rng(seed), W)
    _assert_plans_equal(victim, sizes, budget, priority)


def test_resolve_grants_service_order_and_budget():
    # five thieves hit victim 0 (size 3, budget 4): ranks by worker id,
    # grants to the first three only
    W = 6
    victim = jnp.asarray([-1, 0, 0, 0, 0, 0], jnp.int32)
    sizes = jnp.asarray([3, 0, 0, 0, 0, 0], jnp.int32)
    plan = stealing.resolve_grants(victim, sizes, 4)
    np.testing.assert_array_equal(np.asarray(plan.rank), [0, 0, 1, 2, 3, 4])
    np.testing.assert_array_equal(np.asarray(plan.got),
                                  [False, True, True, True, False, False])
    assert int(plan.taken[0]) == 3


def test_resolve_grants_priority_overrides_id_order():
    W = 4
    victim = jnp.asarray([-1, 0, 0, 0], jnp.int32)
    sizes = jnp.asarray([1, 0, 0, 0], jnp.int32)
    priority = jnp.asarray([0, 9, 5, 1], jnp.int32)
    plan = stealing.resolve_grants(victim, sizes, 4, priority)
    # lowest priority value is served first
    np.testing.assert_array_equal(np.asarray(plan.rank), [0, 2, 1, 0])
    np.testing.assert_array_equal(np.asarray(plan.got),
                                  [False, False, False, True])


def test_segment_prefix_weighted_matches_pairwise():
    rng = np.random.default_rng(7)
    for _ in range(50):
        W = int(rng.integers(1, 40))
        key = rng.integers(0, max(W // 2, 1), W).astype(np.int32)
        active = rng.random(W) < 0.5
        weights = rng.integers(0, 9, W).astype(np.int32)
        got = np.asarray(stealing.segment_prefix(
            jnp.asarray(key), jnp.asarray(active), jnp.asarray(weights)))
        same = (key[:, None] == key[None, :]) & active[:, None] & active[None, :]
        earlier = same & (np.arange(W)[None, :] < np.arange(W)[:, None])
        want = np.where(active,
                        np.sum(np.where(earlier, weights[None, :], 0), axis=1),
                        0)
        np.testing.assert_array_equal(got, want)


def test_grant_width_is_shared_with_kernel():
    from repro.kernels import steal_compact
    assert steal_compact.GMAX == stealing.GRANT_WIDTH


# --------------------------------------------------------------------------- #
# Famine fast-path helpers: emptiness predicate + batched draw replay
# --------------------------------------------------------------------------- #
def test_probe_may_succeed_per_strategy():
    import jax
    mesh = topology.MeshTopology.square(9)
    W = mesh.num_workers
    nbrs = jnp.asarray(stealing.neighbor_list(mesh))
    r2 = jnp.asarray(stealing.radius2_list(mesh))
    nonempty = jnp.zeros((W,), bool).at[8].set(True)  # only corner (2,2)
    fails = jnp.zeros((W,), jnp.int32)
    kw = dict(escalate_after=4, window=64, min_cycle=9, num_workers=W)
    # NEIGHBOR: only the mesh neighbors of worker 8 (5 and 7) may succeed
    near = stealing.probe_may_succeed(stealing.Strategy.NEIGHBOR, nonempty,
                                      fails, nbrs, None, **kw)
    np.testing.assert_array_equal(
        np.asarray(near), np.isin(np.arange(W), [5, 7]))
    # GLOBAL: anyone may draw the nonempty worker
    glob = stealing.probe_may_succeed(stealing.Strategy.GLOBAL, nonempty,
                                      fails, nbrs, None, **kw)
    assert np.asarray(glob).all()
    # ADAPTIVE, fresh thieves in a 64-tick window with 9-tick cycles: can
    # accumulate 4 failures, so the radius-2 set counts too
    ad = stealing.probe_may_succeed(stealing.Strategy.ADAPTIVE, nonempty,
                                    fails, nbrs, r2, **kw)
    np.testing.assert_array_equal(
        np.asarray(ad), np.isin(np.arange(W), [2, 4, 5, 6, 7]))
    # ...but a window too short for (escalate_after - fails) failures keeps
    # radius-2 out of reach: only the direct neighbors remain
    ad_short = stealing.probe_may_succeed(
        stealing.Strategy.ADAPTIVE, nonempty, fails, nbrs, r2,
        escalate_after=4, window=20, min_cycle=9, num_workers=W)
    np.testing.assert_array_equal(np.asarray(ad_short), np.asarray(near))
    # empty mesh: nobody can succeed (the all-famine endgame)
    none = stealing.probe_may_succeed(stealing.Strategy.GLOBAL,
                                      jnp.zeros((W,), bool), fails, nbrs,
                                      None, **kw)
    assert not np.asarray(none).any()


def test_probe_may_succeed_global_respects_components():
    """Route-around reachability: with per-epoch component ids, a GLOBAL
    thief is only risky if a nonempty deque exists in ITS OWN live-link
    component — a nonempty victim across a partition can never be drawn
    into a departing flight, so it must not end famine windows."""
    mesh = topology.MeshTopology.grid(1, 6)
    W = mesh.num_workers
    nbrs = jnp.asarray(stealing.neighbor_list(mesh))
    fails = jnp.zeros((W,), jnp.int32)
    # components {0,1,2} and {3,4,5}; only worker 1 holds work
    comp = jnp.asarray([0, 0, 0, 3, 3, 3], jnp.int32)
    nonempty = jnp.zeros((W,), bool).at[1].set(True)
    kw = dict(escalate_after=4, window=64, min_cycle=3, num_workers=W)
    got = stealing.probe_may_succeed(stealing.Strategy.GLOBAL, nonempty,
                                     fails, nbrs, None, comp_row=comp, **kw)
    # worker 1 itself is NOT risky: GLOBAL draws over *others*, and nobody
    # else in its component holds work
    np.testing.assert_array_equal(np.asarray(got),
                                  [True, False, True, False, False, False])
    # with a holder in each component every NON-holder is risky; the two
    # holders stay non-risky (no other holder in their own component)
    got1 = stealing.probe_may_succeed(
        stealing.Strategy.GLOBAL,
        nonempty.at[4].set(True), fails, nbrs, None, comp_row=comp, **kw)
    np.testing.assert_array_equal(np.asarray(got1),
                                  [True, False, True, True, False, True])
    only_self = stealing.probe_may_succeed(
        stealing.Strategy.GLOBAL,
        jnp.zeros((W,), bool).at[1].set(True), fails, nbrs, None,
        comp_row=jnp.asarray([0, 1, 2, 3, 4, 5], jnp.int32), **kw)
    assert not np.asarray(only_self).any()
    # without comp_row the old conservative any() behavior is preserved
    old = stealing.probe_may_succeed(stealing.Strategy.GLOBAL, nonempty,
                                     fails, nbrs, None, **kw)
    assert np.asarray(old).all()


@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL,
                                      stealing.Strategy.ADAPTIVE])
def test_batched_victim_draws_replay_per_tick_choices(strategy):
    """Row j of the batched tables must reproduce the per-tick choose_*
    draw at tick t0+j bit-for-bit (same fold_in key schedule) for every
    fail count a worker might have at probe time."""
    import jax
    mesh = topology.MeshTopology.square(9)
    W = mesh.num_workers
    nbrs = jnp.asarray(stealing.neighbor_list(mesh))
    r2 = jnp.asarray(stealing.radius2_list(mesh))
    key0 = jax.random.PRNGKey(7)
    t0, count, esc = 123, 6, 4
    near, far = stealing.batched_victim_draws(strategy, key0, t0, count,
                                              nbrs, r2, num_workers=W)
    all_thieves = jnp.ones((W,), bool)
    for j in range(count):
        key = jax.random.fold_in(key0, t0 + j)
        for fv in (0, esc + 1):
            fails = jnp.full((W,), fv, jnp.int32)
            if strategy is stealing.Strategy.NEIGHBOR:
                want = stealing.choose_neighbor(key, nbrs, all_thieves)
                got = near[j]
            elif strategy is stealing.Strategy.GLOBAL:
                want = stealing.choose_global(key, W, all_thieves)
                got = near[j]
            else:
                want = stealing.choose_adaptive(key, nbrs, r2, fails,
                                                all_thieves, esc)
                got = jnp.where(fails >= esc, far[j], near[j])
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batched_victim_draws_linkaware_adaptive():
    """With a link_tau row, the near draw replays the cheapest-live-neighbor
    preference of choose_adaptive_linkaware."""
    import jax
    mesh = topology.MeshTopology.square(9)
    W = mesh.num_workers
    nbrs = jnp.asarray(stealing.neighbor_list(mesh))
    r2 = jnp.asarray(stealing.radius2_list(mesh))
    tau = jnp.asarray(np.arange(4)[None, :] + 2 + np.zeros((W, 1)), jnp.int32)
    key0 = jax.random.PRNGKey(11)
    near, far = stealing.batched_victim_draws(
        stealing.Strategy.ADAPTIVE, key0, 50, 4, nbrs, r2,
        num_workers=W, link_tau_row=tau)
    all_thieves = jnp.ones((W,), bool)
    for j in range(4):
        key = jax.random.fold_in(key0, 50 + j)
        for fv in (0, 9):
            fails = jnp.full((W,), fv, jnp.int32)
            want = stealing.choose_adaptive_linkaware(key, nbrs, r2, tau,
                                                      fails, all_thieves, 4)
            got = jnp.where(fails >= 4, far[j], near[j])
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("strategy,linkaware", [
    (stealing.Strategy.GLOBAL, False),
    (stealing.Strategy.NEIGHBOR, False),
    (stealing.Strategy.LIFELINE, False),
    (stealing.Strategy.ADAPTIVE, False),
    (stealing.Strategy.ADAPTIVE, True),
], ids=["global", "neighbor", "lifeline", "adaptive", "adaptive-linkaware"])
def test_victim_draws_code_equals_batched_rows(strategy, linkaware):
    """The famine replay's per-tick draw, `victim_draws_code` called at
    tick t0 + j under the traced strategy code, equals row j of the enum
    `batched_victim_draws` oracle, near and far, for every code. With a
    link_tau row, ADAPTIVE's near set is the caller-built
    `cheapest_live_table`. LIFELINE's branch is the GLOBAL placeholder
    (the replay is gated off for it)."""
    import jax
    mesh = topology.MeshTopology.grid(4, 5, torus=True)
    W = mesh.num_workers
    nbrs = jnp.asarray(stealing.neighbor_list(mesh))
    r2 = jnp.asarray(stealing.radius2_list(mesh))
    tau = (jnp.asarray((np.arange(W * 4) * 7 % 5 + 1).reshape(W, 4),
                       jnp.int32) if linkaware else None)
    key0 = jax.random.PRNGKey(2**31 + 5)
    t0, count = 1_000_003, 7
    oracle = (stealing.Strategy.GLOBAL
              if strategy is stealing.Strategy.LIFELINE else strategy)
    near, far = stealing.batched_victim_draws(
        oracle, key0, t0, count, nbrs, r2, num_workers=W, link_tau_row=tau)
    if far is None:
        far = near
    adapt = None if tau is None else stealing.cheapest_live_table(nbrs, tau)
    draw = jax.jit(lambda code, t: stealing.victim_draws_code(
        code, key0, t, nbrs, r2, num_workers=W, adaptive_table=adapt))
    code = jnp.int32(stealing.strategy_code(strategy))
    for j in range(count):
        got_near, got_far = draw(code, jnp.int32(t0 + j))
        np.testing.assert_array_equal(np.asarray(got_near),
                                      np.asarray(near[j]))
        np.testing.assert_array_equal(np.asarray(got_far), np.asarray(far[j]))


# --------------------------------------------------------------------------- #
# attach_hops: coords-based pricing ≡ dense hop_matrix oracle
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mesh", [
    topology.MeshTopology.square(16),
    topology.MeshTopology.square(10),              # ragged last row
    topology.MeshTopology.grid(4, 5, torus=True),  # full torus (wrapping metric)
    topology.MeshTopology.grid(1, 6),
], ids=lambda m: f"{m.rows}x{m.cols}{'t' if m.torus else ''}w{m.num_workers}")
def test_attach_hops_matches_dense_matrix_oracle(mesh):
    rng = np.random.default_rng(3)
    W = mesh.num_workers
    victim = rng.integers(-1, W, W).astype(np.int32)
    victim = np.where(victim == np.arange(W), -1, victim)
    sizes = rng.integers(0, 4, W).astype(np.int32)
    plan = stealing.resolve_grants(jnp.asarray(victim), jnp.asarray(sizes))
    got = np.asarray(stealing.attach_hops(plan, mesh).hops)
    h = mesh.hop_matrix  # dense oracle, test-only
    want = np.where(victim >= 0,
                    h[np.arange(W), np.clip(victim, 0, W - 1)], 0)
    np.testing.assert_array_equal(got, want)
    # legacy dense-matrix argument still works but warns — exactly once per
    # call (no internal caller passes the matrix anymore; the coords path
    # is warning-free, asserted above by simply not erroring under -W)
    with pytest.warns(DeprecationWarning) as record:
        legacy = stealing.attach_hops(plan, jnp.asarray(h))
    assert sum(issubclass(w.category, DeprecationWarning)
               for w in record) == 1
    np.testing.assert_array_equal(np.asarray(legacy.hops), want)
    # the supported MeshTopology path never raises the deprecation
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error", DeprecationWarning)
        fresh = stealing.attach_hops(plan, mesh)
    np.testing.assert_array_equal(np.asarray(fresh.hops), want)
