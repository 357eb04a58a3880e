"""High-latency mesh simulator: exactness, latency accounting, fault
tolerance (TC / supervision / malleable pre-shed), stragglers, and the
event-leaping stepper's bit-equivalence with the one-tick oracle."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import arrivals, constellation, linkstate
from repro.core import deque as dq
from repro.core import simulator, stealing, tasks, topology, tracing

FIB = tasks.FibWorkload(n=24, cutoff=10, max_leaf_cost=8)
MESH = topology.MeshTopology.square(16)
EXPECT = FIB.expected_result()


def run(cfg, fail=None, speed=None, wl=FIB, mesh=MESH):
    return simulator.simulate(wl, mesh, cfg, fail_time=fail, speed=speed)


@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL,
                                      stealing.Strategy.ADAPTIVE])
def test_exact_no_failures(strategy):
    cfg = simulator.SimConfig(strategy=strategy, hop_ticks=3, capacity=256,
                              max_ticks=300_000)
    r = run(cfg)
    assert r.result == EXPECT
    assert r.overflow == 0


def test_neighbor_steal_wait_is_2tau():
    """Every completed neighbor attempt costs exactly 2·hop_ticks of waiting
    (assumption (ii): neighbor RTT = 2τ)."""
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR, hop_ticks=4,
                              capacity=256, max_ticks=300_000)
    r = run(cfg)
    # every completed attempt waits 2·hop_ticks (±1 tick of phase-boundary
    # accounting); attempts still in flight at termination wait less
    per_attempt = r.steal_wait_ticks / max(r.attempts, 1)
    assert per_attempt <= 2 * 4
    assert per_attempt >= 2 * 4 * 0.75


def test_global_pays_multihop():
    """Global steals wait ≥ 2τ and on average strictly more (multi-hop)."""
    n_cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                                hop_ticks=5, capacity=256, max_ticks=500_000)
    g_cfg = simulator.SimConfig(strategy=stealing.Strategy.GLOBAL,
                                hop_ticks=5, capacity=256, max_ticks=500_000)
    rn, rg = run(n_cfg), run(g_cfg)
    wait_per_attempt_n = rn.steal_wait_ticks / max(rn.attempts, 1)
    wait_per_attempt_g = rg.steal_wait_ticks / max(rg.attempts, 1)
    assert wait_per_attempt_g > wait_per_attempt_n
    # bytes×hops (congestion) must also be higher for global
    assert rg.bytes_hops / max(rg.attempts, 1) > rn.bytes_hops / max(rn.attempts, 1)


def test_tc_exact_under_failures():
    W = MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[3], ft[7], ft[12] = 100, 250, 400
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR, hop_ticks=3,
                              capacity=256, recovery=simulator.Recovery.TC,
                              ckpt_interval=40, max_ticks=500_000)
    r = run(cfg, fail=ft)
    assert r.result == EXPECT
    assert r.ckpt_bytes > 0


def test_tc_exact_global_strategy_adjacent_failures():
    W = MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[1], ft[2] = 50, 51  # adjacent ticks
    cfg = simulator.SimConfig(strategy=stealing.Strategy.GLOBAL, hop_ticks=2,
                              capacity=256, recovery=simulator.Recovery.TC,
                              ckpt_interval=25, max_ticks=500_000)
    assert run(cfg, fail=ft).result == EXPECT


@pytest.mark.parametrize("schedule", [
    [(1, 50), (2, 51), (3, 52)],              # cascade: rollback resurrects
    [(4, 80), (8, 80), (12, 80)],             # simultaneous at ckpt boundary
    [(1, 50), (2, 50), (5, 90), (6, 130), (9, 170)],
])
def test_tc_exact_adversarial_schedules(schedule):
    """Regression: scatter-clobber in _transplant and snapshot resurrection
    of long-dead workers (both found by these schedules)."""
    W = MESH.num_workers
    ft = -np.ones(W, np.int32)
    for w, t in schedule:
        ft[w] = t
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR, hop_ticks=3,
                              capacity=256, recovery=simulator.Recovery.TC,
                              ckpt_interval=40, max_ticks=500_000)
    assert run(cfg, fail=ft).result == EXPECT


def test_preshed_exact():
    """Malleability (§5/§6): predictable shutdowns with warning lose nothing."""
    W = MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[5], ft[9], ft[14] = 120, 300, 500
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR, hop_ticks=3,
                              capacity=256, preshed=True, warn_ticks=10,
                              max_ticks=500_000)
    assert run(cfg, fail=ft).result == EXPECT


def test_supervision_exact_single_early_failure():
    """Single-level supervision is exact when nothing was re-stolen from the
    dead thief before its death (module docstring's stated guarantee).
    Worker 1 dies at tick 16 holding unfinished stolen work: NO recovery
    provably loses it, supervision's re-push provably restores it."""
    W = MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[1] = 16
    mk = lambda rec: simulator.SimConfig(
        strategy=stealing.Strategy.NEIGHBOR, hop_ticks=3, capacity=256,
        recovery=rec, max_ticks=500_000)
    assert run(mk(simulator.Recovery.NONE), fail=ft).result != EXPECT
    assert run(mk(simulator.Recovery.SUPERVISION), fail=ft).result == EXPECT


def test_supervision_nested_resteal_error_is_bounded_double_count():
    """The documented single-level limitation, measured AND bounded so it
    cannot silently widen: when tasks were re-stolen FROM the thief before
    it died, re-pushing its originally stolen records double-counts the
    emigrated subtrees (exact recovery would need subtree acks — Kestor et
    al. [26]). The error is therefore always an OVERCOUNT by the checksum
    of whole re-stolen subtrees — work is never lost. For this pinned
    schedule exactly one fib(19) subtree emigrated before worker 7 died:
    the deviation is +fib(19) (= 4181) and +231 re-expanded nodes, in both
    step modes. (The schedule depends on the PRNG draw sequence: under
    jax's threefry-partitionable draws, worker 7 dying at t=60 is exact.) If the protocol's accounting changes, this characterization
    must be re-derived — a silent widening (or a loss) fails here."""
    W = MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[7] = 240  # late enough that worker 7's expansions were re-stolen
    deviations = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                                  hop_ticks=3, capacity=256,
                                  recovery=simulator.Recovery.SUPERVISION,
                                  max_ticks=500_000, step_mode=mode)
        r = run(cfg, fail=ft)
        assert r.result != EXPECT  # the nested case really is inexact...
        delta = (r.result - EXPECT) % int(tasks.RESULT_MOD)
        node_excess = r.nodes - FIB.expected_nodes()
        # ...but strictly as a double-count: one fib(19) subtree re-expanded
        assert delta == tasks.fib_mod_table()[19] == 4181, delta
        assert node_excess == 231, node_excess
        deviations[mode] = (delta, node_excess)
    assert deviations["tick"] == deviations["leap"]


def test_no_recovery_loses_work():
    W = MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[5] = 150
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR, hop_ticks=3,
                              capacity=256, recovery=simulator.Recovery.NONE,
                              max_ticks=500_000)
    r = run(cfg, fail=ft)
    assert r.result != EXPECT  # the baseline really does lose work


def test_stragglers_exact_but_slower():
    W = MESH.num_workers
    sp = np.ones(W, np.int32)
    sp[[2, 5, 11]] = 4
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR, hop_ticks=3,
                              capacity=256, max_ticks=500_000)
    r_slow = run(cfg, speed=sp)
    r_fast = run(cfg)
    assert r_slow.result == EXPECT
    assert r_slow.ticks >= r_fast.ticks  # stealing absorbs but can't erase


# --------------------------------------------------------------------------- #
# Event-leaping stepper ≡ one-tick oracle
# --------------------------------------------------------------------------- #
EQ_FIELDS = ("result", "ticks", "nodes", "attempts", "successes",
             "busy_ticks", "steal_wait_ticks", "bytes_hops", "ckpt_bytes",
             "overflow")

EQ_FIB = tasks.FibWorkload(n=20, cutoff=9, max_leaf_cost=8)
EQ_MESH = topology.MeshTopology.square(9)

# strategy × recovery, alternating the {pre-shed, straggler} modifier so
# both appear under every recovery mode and every strategy
EQ_MATRIX = [
    (strat, rec, modifier)
    for si, strat in enumerate([stealing.Strategy.NEIGHBOR,
                                stealing.Strategy.GLOBAL,
                                stealing.Strategy.LIFELINE,
                                stealing.Strategy.ADAPTIVE])
    for ri, rec in enumerate([simulator.Recovery.NONE,
                              simulator.Recovery.TC,
                              simulator.Recovery.SUPERVISION])
    for modifier in [("preshed" if (si + ri) % 2 == 0 else "stragglers")]
]


@pytest.mark.parametrize("strategy,recovery,modifier", EQ_MATRIX)
def test_leap_equals_tick_oracle(strategy, recovery, modifier):
    """Event-leaping `simulate()` returns a SimResult identical to the seed
    one-tick stepper (kept as step_mode="tick") across the full
    strategy × recovery × {pre-shed, straggler} matrix, failures included."""
    W = EQ_MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[2], ft[5] = 70, 150
    speed = None
    preshed, warn = False, 0
    if modifier == "stragglers":
        speed = np.ones(W, np.int32)
        speed[[1, 4]] = 3
    else:
        preshed, warn = True, 8
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(
            strategy=strategy, hop_ticks=3, capacity=128, max_ticks=200_000,
            recovery=recovery, ckpt_interval=30 if recovery is simulator.Recovery.TC else 0,
            preshed=preshed, warn_ticks=warn, step_mode=mode)
        results[mode] = simulator.simulate(EQ_FIB, EQ_MESH, cfg,
                                           fail_time=ft, speed=speed)
    a, b = results["tick"], results["leap"]
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), (
            f"{f}: tick={getattr(a, f)} leap={getattr(b, f)}")
    assert (a.per_worker_busy == b.per_worker_busy).all()
    assert b.events <= b.ticks + 1  # leap iterations = event ticks only


def test_leap_equals_tick_with_steal_kernel():
    """The Pallas grant/export path (interpret mode on CPU) leaves results
    bit-identical to the plain jnp gather, in both step modes."""
    base = dict(strategy=stealing.Strategy.NEIGHBOR, hop_ticks=3,
                capacity=128, max_ticks=200_000)
    res = {}
    for kern in (False, True):
        for mode in ("tick", "leap"):
            cfg = simulator.SimConfig(step_mode=mode, use_steal_kernel=kern, **base)
            res[(kern, mode)] = simulator.simulate(EQ_FIB, EQ_MESH, cfg)
    ref = res[(False, "tick")]
    assert ref.result == EQ_FIB.expected_result()
    for k, r in res.items():
        for f in EQ_FIELDS:
            assert getattr(r, f) == getattr(ref, f), (k, f)


# --------------------------------------------------------------------------- #
# Staged deque-ops backend ≡ per-op loop oracle
# --------------------------------------------------------------------------- #
# Latin-square design over strategy × recovery: every strategy and every
# recovery meets each modifier ({pre-shed, stragglers, dynamic linkstate})
# exactly once, in BOTH step modes — the ISSUE 5 acceptance matrix.
BACKEND_MODS = ("preshed", "stragglers", "linkstate")
BACKEND_MATRIX = [
    (strat, rec, BACKEND_MODS[(si + ri) % 3])
    for si, strat in enumerate([stealing.Strategy.NEIGHBOR,
                                stealing.Strategy.GLOBAL,
                                stealing.Strategy.LIFELINE,
                                stealing.Strategy.ADAPTIVE])
    for ri, rec in enumerate([simulator.Recovery.NONE,
                              simulator.Recovery.TC,
                              simulator.Recovery.SUPERVISION])
]

PW_FIELDS = ("per_worker_busy", "per_worker_overflow", "per_worker_stolen",
             "per_worker_hiwater")


def _backend_case(strategy, recovery, modifier, mode, backend,
                  use_steal_kernel=None):
    W = EQ_MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[2], ft[5] = 70, 150
    speed, ls = None, None
    preshed, warn = False, 0
    if modifier == "stragglers":
        speed = np.ones(W, np.int32)
        speed[[1, 4]] = 3
    elif modifier == "preshed":
        preshed, warn = True, 8
    else:  # dynamic linkstate: oscillating τ + outage epoch + speed epochs
        ls, ft = _dynamic_schedule()
    cfg = simulator.SimConfig(
        strategy=strategy, hop_ticks=3, capacity=128, max_ticks=200_000,
        recovery=recovery,
        ckpt_interval=30 if recovery is simulator.Recovery.TC else 0,
        preshed=preshed, warn_ticks=warn, step_mode=mode,
        deque_backend=backend, use_steal_kernel=use_steal_kernel)
    return simulator.simulate(EQ_FIB, EQ_MESH, cfg, fail_time=ft,
                              speed=speed, linkstate=ls)


@pytest.mark.slow
@pytest.mark.parametrize("strategy,recovery,modifier", BACKEND_MATRIX)
@pytest.mark.parametrize("mode", ["tick", "leap"])
def test_staged_backend_equals_loop_oracle(strategy, recovery, modifier,
                                           mode):
    """Acceptance (ISSUE 5): `deque_backend="staged"` — every per-tick deque
    mutation staged into one fused apply — is bit-identical to the per-op
    `"loop"` oracle across strategy × recovery × {pre-shed, stragglers,
    dynamic linkstate}, in both step modes, per-worker arrays elementwise."""
    a = _backend_case(strategy, recovery, modifier, mode, "loop")
    b = _backend_case(strategy, recovery, modifier, mode, "staged")
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), (
            f"{f}: loop={getattr(a, f)} staged={getattr(b, f)}")
    for f in PW_FIELDS:
        assert (getattr(a, f) == getattr(b, f)).all(), f


def test_staged_backend_kernel_path_equals_loop_oracle():
    """The Pallas interpret path of the staged commit (`deque_apply`) stays
    bit-identical to the loop oracle too — the kernels differ between
    backends (steal_compact exports vs fused applies), the results must
    not."""
    a = _backend_case(stealing.Strategy.NEIGHBOR, simulator.Recovery.NONE,
                      "preshed", "leap", "loop", use_steal_kernel=True)
    b = _backend_case(stealing.Strategy.NEIGHBOR, simulator.Recovery.NONE,
                      "preshed", "leap", "staged", use_steal_kernel=True)
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    for f in PW_FIELDS:
        assert (getattr(a, f) == getattr(b, f)).all(), f


def test_rejects_unknown_deque_backend():
    cfg = simulator.SimConfig(deque_backend="fused")
    with pytest.raises(ValueError):
        simulator.simulate(EQ_FIB, EQ_MESH, cfg)


# --------------------------------------------------------------------------- #
# Deque-occupancy high-water mark (capacity sizing for W >= 4k sweeps)
# --------------------------------------------------------------------------- #
def test_hiwater_bounds_and_empirical_capacity_sizing():
    """`per_worker_hiwater` tracks the running max end-of-tick occupancy:
    bounded by capacity, at least the final occupancy, identical across
    backends and step modes — and usable as an empirical capacity floor
    (re-running with capacity == max hiwater reproduces the run with zero
    overflow, while capacity below it must drop tasks)."""
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              hop_ticks=3, capacity=256, max_ticks=300_000)
    r = run(cfg)
    hw = r.per_worker_hiwater
    assert hw.shape == (MESH.num_workers,)
    assert (hw <= cfg.capacity).all()
    assert hw[0] >= 1          # the root seed alone raises worker 0's mark
    assert hw.max() > 1        # steals spread occupancy beyond the seed
    assert r.overflow == 0

    # bit-identical across step modes and backends (it's part of the state)
    for mode in ("tick", "leap"):
        for backend in ("loop", "staged"):
            r2 = run(dataclasses.replace(cfg, step_mode=mode,
                                         deque_backend=backend))
            np.testing.assert_array_equal(r2.per_worker_hiwater, hw)

    # the empirical-sizing claim: capacity == observed max hiwater loses
    # nothing; one below it overflows
    peak = int(hw.max())
    r_fit = run(dataclasses.replace(cfg, capacity=peak))
    assert r_fit.result == EXPECT and r_fit.overflow == 0
    assert int(r_fit.per_worker_hiwater.max()) == peak
    r_tight = run(dataclasses.replace(cfg, capacity=peak - 1))
    assert r_tight.overflow > 0


def test_hiwater_survives_tc_rollback():
    """Regression (found by review): the high-water mark is an
    observability counter, not simulation state — a TC rollback must not
    erase peaks reached during the discarded ticks (the buffers physically
    held them, so capacity sized to the reported hiwater has to fit the
    pre-rollback segment of a re-run). Pinned as truncation monotonicity:
    extending the horizon across the death/rollback tick can never shrink
    any worker's reported hiwater. The schedule makes the rollback
    maximally destructive — the only snapshot is the near-empty t=0 cut
    (ckpt_interval > death tick), so a rolled-back mark would collapse
    toward the seed one-hot while the buffers demonstrably held their
    pre-death peaks."""
    W = MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[3] = 150
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              hop_ticks=3, capacity=256,
                              recovery=simulator.Recovery.TC,
                              ckpt_interval=200, max_ticks=500_000)
    prev = None
    for horizon in (149, 151, 160, 500_000):
        r = run(dataclasses.replace(cfg, max_ticks=horizon), fail=ft)
        hw = r.per_worker_hiwater
        if prev is not None:
            assert (hw >= prev).all(), (
                f"hiwater shrank when extending the horizon to {horizon}")
        prev = hw
    assert prev.max() > 1      # the pre-death peaks really are on record
    assert r.result == EXPECT  # the sweep's endpoint is the full exact run


def test_hiwater_at_least_final_occupancy_on_truncated_run():
    """On a max_ticks-truncated run the deques are still populated at exit;
    the running max must dominate the final occupancy elementwise (raw
    SimState check — SimResult only carries the mark)."""
    import jax

    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              hop_ticks=3, capacity=256, max_ticks=40)
    ft, wt, fp, sp = simulator._fail_speed_arrays(MESH.num_workers, None, None)
    state, _tr, ticks, _ = simulator._sim_jit(FIB, MESH, cfg.static,
                                              cfg.params,
                                              ft, wt, fp, sp, None)
    assert int(ticks) == 40
    final = np.asarray(state.deque.size)
    assert final.sum() > 0      # truly truncated mid-run
    assert (np.asarray(state.hiwater) >= final).all()
    assert (np.asarray(state.hiwater) <= cfg.capacity).all()


# --------------------------------------------------------------------------- #
# Time-varying link state (linkstate subsystem)
# --------------------------------------------------------------------------- #
def _dynamic_schedule():
    """Non-trivial schedule on EQ_MESH: oscillating inter-row τ, a link-down
    epoch around worker 4, per-epoch straggler speeds — plus an eclipse
    (predictable death of worker 4 at the outage epoch, pre-shed warned)."""
    W = EQ_MESH.num_workers
    starts = np.asarray([0, 37, 60, 95, 150, 300], np.int32)
    E = len(starts)
    tau = np.ones((E, W, 4), np.int32)
    up = np.ones((E, W, 4), bool)
    speed = np.ones((E, W), np.int32)
    nbr = EQ_MESH.neighbor_table
    for e in range(E):
        tau[e, :, linkstate.NORTH] = tau[e, :, linkstate.SOUTH] = 2 + (e % 3)
        tau[e, :, linkstate.WEST] = tau[e, :, linkstate.EAST] = 3
    for d in range(4):  # epoch 2: worker 4 enters eclipse, its links go dark
        if nbr[4, d] >= 0:
            up[2, 4, d] = False
            up[2, nbr[4, d], linkstate.OPPOSITE[d]] = False
    speed[3, [1, 5]] = 3
    ls = linkstate.LinkStateSchedule(starts, tau, up, speed).validate(EQ_MESH)
    ft = -np.ones(W, np.int32)
    ft[4] = 60
    return ls, ft


@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL,
                                      stealing.Strategy.ADAPTIVE])
def test_leap_equals_tick_dynamic_linkstate(strategy):
    """Acceptance: the event-leaping stepper stays bit-identical to the
    one-tick oracle under a non-trivial time-varying schedule (oscillating
    τ + a link-down epoch + an eclipse shutdown + speed epochs)."""
    ls, ft = _dynamic_schedule()
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=strategy, capacity=128,
                                  max_ticks=200_000, step_mode=mode,
                                  preshed=True, warn_ticks=8)
        results[mode] = simulator.simulate(EQ_FIB, EQ_MESH, cfg,
                                           fail_time=ft, linkstate=ls)
    a, b = results["tick"], results["leap"]
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), (
            f"{f}: tick={getattr(a, f)} leap={getattr(b, f)}")
    assert (a.per_worker_busy == b.per_worker_busy).all()
    assert b.events <= b.ticks + 1


@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL,
                                      stealing.Strategy.ADAPTIVE])
def test_static_linkstate_equals_scalar_hop_ticks(strategy):
    """The degenerate single-epoch uniform schedule reproduces the scalar
    `hop_ticks` path bit-for-bit (ADAPTIVE included: with uniform τ the
    cheapest-live-neighbor pick reduces to the uniform neighbor pick)."""
    ls = linkstate.LinkStateSchedule.static(EQ_MESH, 3)
    cfg = simulator.SimConfig(strategy=strategy, hop_ticks=3, capacity=128,
                              max_ticks=200_000)
    a = simulator.simulate(EQ_FIB, EQ_MESH, cfg)
    b = simulator.simulate(EQ_FIB, EQ_MESH, cfg, linkstate=ls)
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    assert (a.per_worker_busy == b.per_worker_busy).all()


def test_constellation_schedule_exact_with_preshed():
    """End-to-end: a constellation-emitted dynamic schedule (oscillation,
    eclipse dark links, seam handovers) with malleable pre-shed loses no
    work, and leap stays equal to tick."""
    ccfg = constellation.ConstellationConfig(
        planes=3, sats_per_plane=3, orbit_ticks=400, tau_base=3,
        battery_limited_frac=0.3, warn_ticks=20, wraparound=True,
        epochs_per_orbit=8, seam_outage_frac=0.15, seed=5)
    con = constellation.Constellation(ccfg)
    sched = con.schedule(horizon_ticks=800)
    pred_fail = np.where(sched.predictable, sched.fail_time, -1).astype(np.int32)
    assert (pred_fail >= 0).any()
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=stealing.Strategy.ADAPTIVE,
                                  capacity=128, max_ticks=200_000,
                                  step_mode=mode, preshed=True,
                                  warn_ticks=ccfg.warn_ticks)
        results[mode] = simulator.simulate(EQ_FIB, con.mesh, cfg,
                                           fail_time=pred_fail,
                                           linkstate=sched.linkstate)
    a, b = results["tick"], results["leap"]
    assert a.result == EQ_FIB.expected_result()
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), f


def test_linkstate_speed_epochs_replace_speed_arg():
    """Straggler divisors ride in the schedule's per-epoch `speed`; passing
    both the static `speed` argument and a schedule is rejected."""
    W = EQ_MESH.num_workers
    sp = np.ones(W, np.int32)
    sp[[2, 5]] = 4
    ls = linkstate.LinkStateSchedule.static(EQ_MESH, 3, speed=sp)
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              hop_ticks=3, capacity=128, max_ticks=200_000)
    a = simulator.simulate(EQ_FIB, EQ_MESH, cfg, speed=sp)
    b = simulator.simulate(EQ_FIB, EQ_MESH, cfg, linkstate=ls)
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    with pytest.raises(ValueError):
        simulator.simulate(EQ_FIB, EQ_MESH, cfg, speed=sp, linkstate=ls)


def test_simulate_batch_matches_serial_with_linkstate():
    ls, ft = _dynamic_schedule()
    seeds = [0, 3]
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              capacity=128, max_ticks=200_000)
    batch = simulator.simulate_batch(EQ_FIB, EQ_MESH, cfg, seeds=seeds,
                                     fail_time=ft, linkstate=ls)
    for s, rb in zip(seeds, batch):
        rs = simulator.simulate(EQ_FIB, EQ_MESH,
                                dataclasses.replace(cfg, seed=s),
                                fail_time=ft, linkstate=ls)
        for f in EQ_FIELDS:
            assert getattr(rb, f) == getattr(rs, f), (s, f)


def test_simulate_batch_matches_serial():
    """The vmapped batch driver returns per-seed results identical to
    serial `simulate` calls."""
    seeds = [0, 1, 2]
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR, hop_ticks=3,
                              capacity=128, max_ticks=200_000)
    batch = simulator.simulate_batch(EQ_FIB, EQ_MESH, cfg, seeds=seeds)
    for s, rb in zip(seeds, batch):
        rs = simulator.simulate(EQ_FIB, EQ_MESH,
                                dataclasses.replace(cfg, seed=s))
        for f in EQ_FIELDS:
            assert getattr(rb, f) == getattr(rs, f), (s, f)


# --------------------------------------------------------------------------- #
# Route-around detours + eclipse wake-ups: leap ≡ tick conformance matrix
# --------------------------------------------------------------------------- #
CONF_TORUS = topology.MeshTopology.grid(3, 3, torus=True)
CONF_WAKE_WL = tasks.FibWorkload(n=16, cutoff=12, max_leaf_cost=96)
# long enough that every strategy × τ of the periodic-eclipse scenario is
# still running at the second-cycle wake (t=110)
CONF_CYCLE_WL = tasks.FibWorkload(n=17, cutoff=12, max_leaf_cost=96)


def _conf_seam_outage(tau):
    """Seam outage with detours: the row-wrap links of the 3x3 torus go dark
    in alternating epochs, and inter-row τ ≠ intra-row τ, so cross-seam
    flights reprice from a 1-hop wrap to a 2-hop route-around detour."""
    mesh = CONF_TORUS
    W = mesh.num_workers
    starts = np.asarray([0, 25, 70, 115], np.int32)
    E = len(starts)
    tau_tab = np.full((E, W, 4), int(tau), np.int32)
    tau_tab[:, :, linkstate.NORTH] = tau_tab[:, :, linkstate.SOUTH] = int(tau) + 1
    up = np.ones((E, W, 4), bool)
    rows = mesh.coords[:, 0]
    for e in (1, 3):  # seam dark while thieves are mid-flight across it
        up[e, rows == 0, linkstate.NORTH] = False
        up[e, rows == mesh.rows - 1, linkstate.SOUTH] = False
    ls = linkstate.LinkStateSchedule(
        starts, tau_tab, up, np.ones((E, W), np.int32)).validate(mesh)
    return mesh, EQ_FIB, ls, None, None


def _conf_eclipse_cycle(tau):
    """Eclipse enter→exit: worker 4 (center) dies at t=3 with pre-shed
    warning, its links dark while asleep, and it WAKES at t=60 with links
    restored — early enough in the run that it is stolen from post-wake
    (asserted: its pre-death window is provably too short to acquire work,
    so any grant out of its deque happened after the wake)."""
    mesh = EQ_MESH
    W = mesh.num_workers
    starts = np.asarray([0, 3, 60, 110], np.int32)
    E = len(starts)
    tau_tab = np.full((E, W, 4), int(tau), np.int32)
    for e in range(E):
        tau_tab[e, :, linkstate.NORTH] = tau_tab[e, :, linkstate.SOUTH] = \
            int(tau) + (e % 2)
    up = np.ones((E, W, 4), bool)
    nbr = mesh.neighbor_table
    for d in range(4):  # dark from entry (epoch 1) to wake (epoch 2)
        if nbr[4, d] >= 0:
            up[1, 4, d] = False
            up[1, nbr[4, d], linkstate.OPPOSITE[d]] = False
    ls = linkstate.LinkStateSchedule(
        starts, tau_tab, up, np.ones((E, W), np.int32)).validate(mesh)
    ft = -np.ones(W, np.int32)
    wt = -np.ones(W, np.int32)
    ft[4], wt[4] = 3, 60
    return mesh, EQ_FIB, ls, ft, wt


def _conf_midfamine_wake(tau):
    """Mid-famine wake-up: few long leaves keep thieves churning on empty
    deques; worker 5 sleeps through the opening spread and wakes into the
    famine stretch, forcing the famine window to end at the wake tick."""
    mesh = EQ_MESH
    W = mesh.num_workers
    starts = np.asarray([0, 5, 80, 140], np.int32)
    E = len(starts)
    tau_tab = np.full((E, W, 4), int(tau), np.int32)
    for e in range(E):
        tau_tab[e, :, linkstate.NORTH] = tau_tab[e, :, linkstate.SOUTH] = \
            int(tau) + (e % 2)
    up = np.ones((E, W, 4), bool)
    nbr = mesh.neighbor_table
    for d in range(4):
        if nbr[5, d] >= 0:
            up[1, 5, d] = False
            up[1, nbr[5, d], linkstate.OPPOSITE[d]] = False
    ls = linkstate.LinkStateSchedule(
        starts, tau_tab, up, np.ones((E, W), np.int32)).validate(mesh)
    ft = -np.ones(W, np.int32)
    wt = -np.ones(W, np.int32)
    ft[5], wt[5] = 5, 80
    return mesh, CONF_WAKE_WL, ls, ft, wt


CONF_SCENARIOS = {
    "seam_detour": _conf_seam_outage,
    "eclipse_cycle": _conf_eclipse_cycle,
    "midfamine_wake": _conf_midfamine_wake,
}


@pytest.mark.slow
@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL,
                                      stealing.Strategy.ADAPTIVE])
@pytest.mark.parametrize("scenario", list(CONF_SCENARIOS))
@pytest.mark.parametrize("tau", [1, 5])
def test_leap_equals_tick_conformance_matrix(strategy, scenario, tau):
    """Acceptance: the event-leaping stepper stays bit-identical to the
    one-tick oracle under the new route-around + wake-up semantics, for
    every strategy × {seam outage with detours, eclipse enter+exit,
    mid-famine wake-up} × τ ∈ {1, 5} — the same way PR 1–3 pinned their
    semantics. Per-worker busy / overflow / victim-side stolen counts are
    asserted elementwise, not just the scalar stats."""
    mesh, wl, ls, ft, wt = CONF_SCENARIOS[scenario](tau)
    preshed = ft is not None
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=strategy, capacity=128,
                                  max_ticks=200_000, step_mode=mode,
                                  preshed=preshed,
                                  warn_ticks=2 if preshed else 0)
        results[mode] = simulator.simulate(wl, mesh, cfg, fail_time=ft,
                                           linkstate=ls, wake_time=wt)
    a, b = results["tick"], results["leap"]
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), (
            f"{f}: tick={getattr(a, f)} leap={getattr(b, f)}")
    assert (a.per_worker_busy == b.per_worker_busy).all()
    assert (a.per_worker_overflow == b.per_worker_overflow).all()
    assert (a.per_worker_stolen == b.per_worker_stolen).all()
    assert b.events <= b.ticks + 1
    if scenario == "eclipse_cycle":
        # pre-shed keeps the cycle exact, and the woken worker rejoined the
        # victim set: tasks were granted out of ITS deque, which it can
        # only have filled post-wake (it died at t=3, before any loot
        # could reach it).
        assert a.result == wl.expected_result()
        assert a.per_worker_stolen[4] > 0
        assert a.per_worker_busy[4] > 3
    if scenario == "midfamine_wake":
        # the famine fast path still collapses the churn around the wake
        assert b.events < b.ticks, (b.events, b.ticks)


def test_wake_up_worker_is_stolen_from_post_wake():
    """Elastic grow on a 1x3 line, where the claim 'the woken worker is
    stolen from post-wake' is airtight by topology: endpoint worker 2's
    ONLY victim is the middle worker 1, which is dead from t=2 until its
    wake and provably never held a task before dying — so busy[2] > 0 and
    stolen_from[1] > 0 can only arise from post-wake steals. A no-wake
    control run shows both pinned at 0."""
    mesh = topology.MeshTopology.grid(1, 3)
    wl = tasks.FibWorkload(n=18, cutoff=9, max_leaf_cost=12)
    W = 3
    ft = -np.ones(W, np.int32)
    wt = -np.ones(W, np.int32)
    ft[1], wt[1] = 2, 40
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                                  hop_ticks=2, capacity=128,
                                  max_ticks=200_000, preshed=True,
                                  warn_ticks=1, step_mode=mode)
        results[mode] = simulator.simulate(wl, mesh, cfg, fail_time=ft,
                                           wake_time=wt)
    a, b = results["tick"], results["leap"]
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    assert (a.per_worker_stolen == b.per_worker_stolen).all()
    assert a.result == wl.expected_result()
    assert a.per_worker_stolen[1] > 0   # the woken worker was robbed...
    assert a.per_worker_busy[2] > 0     # ...by the worker it unblocked
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              hop_ticks=2, capacity=128, max_ticks=200_000,
                              preshed=True, warn_ticks=1)
    dead = simulator.simulate(wl, mesh, cfg, fail_time=ft)
    assert dead.per_worker_stolen[1] == 0
    assert dead.per_worker_busy[2] == 0
    assert a.ticks < dead.ticks  # the rejoin visibly helps the makespan


def test_partitioned_workers_are_unreachable_not_cheap():
    """Route-around acceptance: severing the single link of a 1x4 line
    partitions workers {2, 3} away from the root's component. Under the old
    semantics GLOBAL flights would be priced straight through the dead link
    and the far side would receive work; now those flights never depart —
    the far side stays at exactly zero busy ticks while the run completes
    exactly on the near side, in both step modes."""
    mesh = topology.MeshTopology.grid(1, 4)
    W = 4
    lt = np.full((1, W, 4), 2, np.int32)
    lu = np.ones((1, W, 4), bool)
    lu[0, 1, linkstate.EAST] = False
    lu[0, 2, linkstate.WEST] = False
    ls = linkstate.LinkStateSchedule(
        np.zeros(1, np.int32), lt, lu,
        np.ones((1, W), np.int32)).validate(mesh)
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=stealing.Strategy.GLOBAL,
                                  capacity=128, max_ticks=200_000,
                                  step_mode=mode)
        r = simulator.simulate(EQ_FIB, mesh, cfg, linkstate=ls)
        assert r.result == EQ_FIB.expected_result()
        assert r.per_worker_busy[2] == 0 and r.per_worker_busy[3] == 0
        assert r.per_worker_stolen[2] == 0 and r.per_worker_stolen[3] == 0
        assert r.per_worker_busy[0] > 0 and r.per_worker_busy[1] > 0


def test_wake_time_requires_prior_death():
    cfg = simulator.SimConfig()
    wt = np.full(EQ_MESH.num_workers, 5, np.int32)
    with pytest.raises(ValueError):
        simulator.simulate(EQ_FIB, EQ_MESH, cfg, wake_time=wt)
    ft = -np.ones(EQ_MESH.num_workers, np.int32)
    ft[3] = 10
    wt = -np.ones(EQ_MESH.num_workers, np.int32)
    wt[3] = 10  # not strictly after the death
    with pytest.raises(ValueError):
        simulator.simulate(EQ_FIB, EQ_MESH, cfg, fail_time=ft, wake_time=wt)


# --------------------------------------------------------------------------- #
# Famine-churn regime: probe-cycle batching ≡ one-tick oracle
# --------------------------------------------------------------------------- #
# Few long leaves on many workers: most of the run is idle thieves
# re-probing empty victims at 2τ cadence — the regime whose events used to
# cap the leap factor at ~1 (paper §3.1 immediate retry; ROADMAP "Leap the
# famine-churn regime").
FAMINE_WL = tasks.FibWorkload(n=16, cutoff=12, max_leaf_cost=96)


def _famine_linkstate(tau):
    """Two epoch flips (τ oscillation on the row links) landing mid-famine."""
    W = EQ_MESH.num_workers
    starts = np.asarray([0, 45, 110], np.int32)
    E = len(starts)
    tau_tab = np.full((E, W, 4), int(tau), np.int32)
    for e in range(E):
        tau_tab[e, :, linkstate.NORTH] = tau_tab[e, :, linkstate.SOUTH] = \
            int(tau) + (e % 2)
    return linkstate.LinkStateSchedule(
        epoch_starts=starts, link_tau=tau_tab,
        link_up=np.ones((E, W, 4), bool),
        speed=np.ones((E, W), np.int32)).validate(EQ_MESH)


@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.ADAPTIVE])
@pytest.mark.parametrize("tau", [1, 5])
def test_leap_equals_tick_famine_regime(strategy, tau):
    """Acceptance: in the famine-churn regime — with a mid-famine link-state
    epoch flip AND a mid-famine failure — the batched probe-cycle path
    stays bit-identical to the one-tick oracle, and actually collapses
    loop iterations below the tick count."""
    W = EQ_MESH.num_workers
    ft = -np.ones(W, np.int32)
    ft[5] = 70  # lands while thieves churn
    ls = _famine_linkstate(tau)
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=strategy, capacity=64,
                                  max_ticks=100_000, step_mode=mode)
        results[mode] = simulator.simulate(FAMINE_WL, EQ_MESH, cfg,
                                           fail_time=ft, linkstate=ls)
    a, b = results["tick"], results["leap"]
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), (
            f"{f}: tick={getattr(a, f)} leap={getattr(b, f)}")
    assert (a.per_worker_busy == b.per_worker_busy).all()
    assert (a.per_worker_overflow == b.per_worker_overflow).all()
    # the famine fast path must fire: iterations well below tick count
    assert b.events < b.ticks // 2, (b.events, b.ticks)


@pytest.mark.parametrize("tau", [1, 5])
def test_famine_batch_size_never_changes_results(tau):
    """Property: the reported leap factor is >= 1 and the famine batch size
    (including 0 = disabled) only trades iterations for per-iteration work
    — every setting reproduces the identical SimResult."""
    ref = None
    for fb in (0, 1, 7, 64, 256):
        cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                                  hop_ticks=tau, capacity=64,
                                  max_ticks=100_000, famine_batch=fb)
        r = simulator.simulate(FAMINE_WL, EQ_MESH, cfg)
        assert r.events <= r.ticks + 1  # leap factor >= 1 (modulo final iter)
        if ref is None:
            ref = r
        else:
            for f in EQ_FIELDS:
                assert getattr(r, f) == getattr(ref, f), (fb, f)
            assert (r.per_worker_busy == ref.per_worker_busy).all()
    assert ref.result == FAMINE_WL.expected_result()


def test_per_worker_overflow_sums_and_famine_batch_invariant_linkstate():
    """Property (extends the PR 3 sweep to the linkstate path): under a
    dynamic link-state schedule with an outage epoch and a capacity small
    enough to actually drop tasks, `per_worker_overflow` always sums to the
    scalar overflow, and famine_batch ∈ {0, 1, 7, 64, 256} reproduces the
    identical result — per-worker breakdown included."""
    ls, ft = _dynamic_schedule()
    ref = None
    for fb in (0, 1, 7, 64, 256):
        cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                                  capacity=2, max_ticks=200_000,
                                  preshed=True, warn_ticks=8,
                                  famine_batch=fb)
        r = simulator.simulate(EQ_FIB, EQ_MESH, cfg, fail_time=ft,
                               linkstate=ls)
        assert r.overflow == int(r.per_worker_overflow.sum())
        assert r.overflow > 0  # capacity 2 really does drop tasks
        if ref is None:
            ref = r
        else:
            for f in EQ_FIELDS:
                assert getattr(r, f) == getattr(ref, f), (fb, f)
            assert (r.per_worker_overflow == ref.per_worker_overflow).all()
            assert (r.per_worker_stolen == ref.per_worker_stolen).all()


def test_famine_window_ends_at_midflight_refill():
    """Regression: a thief stealing EMPTY-HANDED whose own deque is refilled
    mid-flight (supervision re-push after its earlier robber dies) must end
    the famine window at its flight transition — the batched replay has no
    expansion path, so skipping past its post-delivery pop desynchronized
    leap from tick (found by review; the earlier regression only covered
    the got=True variant, which the delivery horizon already caught)."""
    mesh = topology.MeshTopology.grid(1, 2)
    ft = np.asarray([-1, 255], np.int32)
    results = {}
    for mode, fb in (("tick", 64), ("leap", 64), ("leap", 0)):
        cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                                  hop_ticks=3, capacity=64,
                                  recovery=simulator.Recovery.SUPERVISION,
                                  max_ticks=100_000, step_mode=mode,
                                  famine_batch=fb)
        results[(mode, fb)] = simulator.simulate(FAMINE_WL, mesh, cfg,
                                                 fail_time=ft)
    ref = results[("tick", 64)]
    for key, r in results.items():
        for f in EQ_FIELDS:
            assert getattr(r, f) == getattr(ref, f), (key, f)
        assert (r.per_worker_busy == ref.per_worker_busy).all()


def test_famine_batch_rejects_negative():
    cfg = simulator.SimConfig(famine_batch=-1)
    with pytest.raises(ValueError):
        simulator.simulate(FAMINE_WL, EQ_MESH, cfg)


# --------------------------------------------------------------------------- #
# _transplant: overflow accounting and multi-source-per-heir ordering
# --------------------------------------------------------------------------- #
def _mk_deque(rows, cap):
    """Build a DequeState from per-worker task lists (bottom→top)."""
    W = len(rows)
    state = dq.make(W, cap)
    buf = np.zeros((W, cap, dq.TASK_WIDTH), np.int32)
    size = np.zeros(W, np.int32)
    for w, tasks_ in enumerate(rows):
        for i, t in enumerate(tasks_):
            buf[w, i] = t
        size[w] = len(tasks_)
    return dq.DequeState(jnp.asarray(buf), state.bot, jnp.asarray(size))


def test_transplant_multi_source_per_heir_ordering():
    """Two dead sources with the same heir append in worker-id order,
    each preserving its own bottom→top order, after the heir's tasks."""
    cap = 8
    rows = [[(9, 0, 0, 0)],                       # heir 0
            [(1, 1, 0, 0), (1, 2, 0, 0)],         # source 1
            [(2, 1, 0, 0)],                       # source 2
            []]
    deq = _mk_deque(rows, cap)
    acc = jnp.asarray([5, 7, 11, 0], jnp.int32)
    src = jnp.asarray([False, True, True, False])
    heir = jnp.asarray([0, 0, 0, 0], jnp.int32)
    out, new_acc, ovf = simulator._transplant(deq, acc, src, heir,
                                              jnp.zeros(4, jnp.int32))
    assert dq.to_list(out, 0) == [(9, 0, 0, 0), (1, 1, 0, 0), (1, 2, 0, 0),
                                  (2, 1, 0, 0)]
    np.testing.assert_array_equal(np.asarray(out.size), [4, 0, 0, 0])
    np.testing.assert_array_equal(np.asarray(new_acc), [23, 0, 0, 0])
    assert int(ovf.sum()) == 0


def test_transplant_overflow_accounting():
    """Writes beyond the heir's capacity are dropped and counted, including
    a later source finding no room after an earlier source filled it."""
    cap = 4
    rows = [[(9, 0, 0, 0), (9, 1, 0, 0)],          # heir: 2/4 full
            [(1, 1, 0, 0), (1, 2, 0, 0), (1, 3, 0, 0)],  # brings 3, room 2
            [(2, 1, 0, 0)]]                        # brings 1, room 0
    deq = _mk_deque(rows, cap)
    acc = jnp.zeros(3, jnp.int32)
    src = jnp.asarray([False, True, True])
    heir = jnp.asarray([0, 0, 0], jnp.int32)
    out, _, ovf = simulator._transplant(deq, acc, src, heir,
                                        jnp.zeros(3, jnp.int32))
    assert dq.to_list(out, 0) == [(9, 0, 0, 0), (9, 1, 0, 0), (1, 1, 0, 0),
                                  (1, 2, 0, 0)]
    np.testing.assert_array_equal(np.asarray(out.size), [4, 0, 0])
    # one dropped from source 1, one from source 2 — both charged to heir 0
    np.testing.assert_array_equal(np.asarray(ovf), [2, 0, 0])


def test_transplant_ring_wraparound():
    """Appends respect the ring structure when the heir's window wraps."""
    cap = 4
    deq = _mk_deque([[(9, 0, 0, 0)], [(1, 1, 0, 0), (1, 2, 0, 0)]], cap)
    # rotate the heir's ring so its bottom sits near the end
    buf = np.asarray(deq.buf).copy()
    buf[0] = np.roll(buf[0], 3, axis=0)
    deq = dq.DequeState(jnp.asarray(buf), jnp.asarray([3, 0], jnp.int32),
                        deq.size)
    assert dq.to_list(deq, 0) == [(9, 0, 0, 0)]
    src = jnp.asarray([False, True])
    heir = jnp.asarray([0, 0], jnp.int32)
    out, _, ovf = simulator._transplant(deq, jnp.zeros(2, jnp.int32), src,
                                        heir, jnp.zeros(2, jnp.int32))
    assert dq.to_list(out, 0) == [(9, 0, 0, 0), (1, 1, 0, 0), (1, 2, 0, 0)]
    assert int(ovf.sum()) == 0


def test_import_overflow_reported_not_swallowed():
    """Regression: a loot delivery landing on a FULL capacity-1 deque is a
    real task loss and must be counted, with a per-worker breakdown.

    Scenario (found by instrumented search, deterministic under seed 0):
    on a 1x3 line with capacity-1 deques under SUPERVISION recovery,
    worker 0 is robbed by worker 1 (supervision records the theft), then
    goes stealing itself; worker 1 dies at tick 6 while worker 0's loot is
    still in flight, so the supervision re-push refills worker 0's deque
    and the delivery at tick 7 finds it full. Before this fix the dropped
    import was silently swallowed (worker 0 would report 26 expansion
    drops instead of 27).
    """
    mesh = topology.MeshTopology.grid(1, 3)
    wl = tasks.FibWorkload(n=30, cutoff=4, max_leaf_cost=8)
    ft = np.asarray([-1, 6, -1], np.int32)
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                                  hop_ticks=3, capacity=1, max_ticks=20_000,
                                  recovery=simulator.Recovery.SUPERVISION,
                                  step_mode=mode)
        results[mode] = simulator.simulate(wl, mesh, cfg, fail_time=ft)
    for r in results.values():
        assert r.overflow == 28
        np.testing.assert_array_equal(r.per_worker_overflow, [27, 1, 0])
        assert r.overflow == int(r.per_worker_overflow.sum())
    assert results["tick"].ticks == results["leap"].ticks


def test_per_worker_overflow_zero_when_capacity_suffices():
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              hop_ticks=3, capacity=256, max_ticks=300_000)
    r = run(cfg)
    assert r.overflow == 0
    np.testing.assert_array_equal(r.per_worker_overflow,
                                  np.zeros(MESH.num_workers, np.int32))


def test_neighbor_beats_global_at_high_latency():
    """The paper's central prediction (§3.3): with real hop latency,
    neighbor-only finishes sooner."""
    wl = tasks.FibWorkload(n=26, cutoff=10, max_leaf_cost=8)
    mesh = topology.MeshTopology.square(25)
    times = {}
    for strat in (stealing.Strategy.NEIGHBOR, stealing.Strategy.GLOBAL):
        cfg = simulator.SimConfig(strategy=strat, hop_ticks=8, capacity=256,
                                  max_ticks=1_000_000)
        times[strat] = simulator.simulate(wl, mesh, cfg).ticks
    assert times[stealing.Strategy.NEIGHBOR] < times[stealing.Strategy.GLOBAL]


# --------------------------------------------------------------------------- #
# Periodic (fail, wake) schedules
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL])
@pytest.mark.parametrize("mode", ["tick", "leap"])
def test_periodic_single_cycle_bit_identical_to_scalar_wake(strategy, mode):
    """Satellite regression: a periodic (fail, wake) schedule whose second
    cycle lies beyond the horizon is the scalar `wake_time=` schedule —
    every scalar stat AND every per-worker array must match elementwise."""
    W = EQ_MESH.num_workers
    ft = -np.ones(W, np.int32)
    wt = -np.ones(W, np.int32)
    ft[4], wt[4] = 40, 90
    fp = -np.ones(W, np.int32)
    fp[4] = 1 << 20                      # one cycle: next fire > max_ticks
    cfg = simulator.SimConfig(strategy=strategy, hop_ticks=2, capacity=128,
                              max_ticks=200_000, step_mode=mode,
                              preshed=True, warn_ticks=10)
    a = simulator.simulate(EQ_FIB, EQ_MESH, cfg, fail_time=ft, wake_time=wt)
    b = simulator.simulate(EQ_FIB, EQ_MESH, cfg, fail_time=ft, wake_time=wt,
                           fail_period=fp)
    for f in EQ_FIELDS + ("events",):
        assert getattr(a, f) == getattr(b, f), (
            f"{f}: scalar={getattr(a, f)} periodic={getattr(b, f)}")
    np.testing.assert_array_equal(a.per_worker_busy, b.per_worker_busy)
    np.testing.assert_array_equal(a.per_worker_overflow, b.per_worker_overflow)
    np.testing.assert_array_equal(a.per_worker_stolen, b.per_worker_stolen)
    np.testing.assert_array_equal(a.per_worker_hiwater, b.per_worker_hiwater)


def test_fail_period_validation():
    W = EQ_MESH.num_workers
    ft = -np.ones(W, np.int32)
    wt = -np.ones(W, np.int32)
    fp = -np.ones(W, np.int32)
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              max_ticks=100)
    ft[2], wt[2] = 10, 20
    for bad in (0, -3, 5, 1 << 29):      # zero/negative, wake outside cycle,
        fp[2] = bad                       # int32-unsafe cycle
        with pytest.raises(ValueError):
            simulator.simulate(EQ_FIB, EQ_MESH, cfg, fail_time=ft,
                               wake_time=wt, fail_period=fp)
    fp[2] = 50                           # period without a wake
    with pytest.raises(ValueError):
        simulator.simulate(EQ_FIB, EQ_MESH, cfg, fail_time=ft,
                           fail_period=fp)


def _conf_second_cycle_wake(tau):
    """Periodic eclipse on the mid-famine scenario: worker 5 sleeps in
    [5, 40) and again in [75, 110) (period 70); the long-leaf workload
    keeps thieves churning on empty deques, so the SECOND-cycle wake lands
    inside a certified famine window and must clip it exactly as the
    first-cycle wake did. Link epochs mirror both sleep intervals."""
    mesh = EQ_MESH
    W = mesh.num_workers
    starts = np.asarray([0, 5, 40, 75, 110, 145, 180], np.int32)
    E = len(starts)
    tau_tab = np.full((E, W, 4), int(tau), np.int32)
    for e in range(E):
        tau_tab[e, :, linkstate.NORTH] = tau_tab[e, :, linkstate.SOUTH] = \
            int(tau) + (e % 2)
    up = np.ones((E, W, 4), bool)
    nbr = mesh.neighbor_table
    for e in (1, 3):                     # dark during both sleep cycles
        for d in range(4):
            if nbr[5, d] >= 0:
                up[e, 5, d] = False
                up[e, nbr[5, d], linkstate.OPPOSITE[d]] = False
    ls = linkstate.LinkStateSchedule(
        starts, tau_tab, up, np.ones((E, W), np.int32)).validate(mesh)
    ft = -np.ones(W, np.int32)
    wt = -np.ones(W, np.int32)
    fp = -np.ones(W, np.int32)
    ft[5], wt[5], fp[5] = 5, 40, 70
    return mesh, CONF_CYCLE_WL, ls, ft, wt, fp


@pytest.mark.slow
@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL,
                                      stealing.Strategy.ADAPTIVE])
@pytest.mark.parametrize("tau", [1, 5])
def test_second_cycle_wake_clips_famine_window(strategy, tau):
    """Satellite: extends PR 4's conformance matrix — a mid-famine wake in
    the SECOND eclipse cycle terminates the certified famine window exactly
    like a first-cycle wake (leap ≡ tick bit-identical, fast path active)."""
    mesh, wl, ls, ft, wt, fp = _conf_second_cycle_wake(tau)
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=strategy, capacity=128,
                                  max_ticks=200_000, step_mode=mode,
                                  preshed=True, warn_ticks=2)
        results[mode] = simulator.simulate(wl, mesh, cfg, fail_time=ft,
                                           linkstate=ls, wake_time=wt,
                                           fail_period=fp)
    a, b = results["tick"], results["leap"]
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), (
            f"{f}: tick={getattr(a, f)} leap={getattr(b, f)}")
    np.testing.assert_array_equal(a.per_worker_busy, b.per_worker_busy)
    np.testing.assert_array_equal(a.per_worker_stolen, b.per_worker_stolen)
    assert a.ticks > 110     # the run actually reaches the second-cycle wake
    assert b.events < b.ticks  # famine churn still collapses around wakes


@pytest.mark.slow
@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL,
                                      stealing.Strategy.ADAPTIVE])
@pytest.mark.parametrize("scenario", ["seam_detour", "multi_cycle_eclipse"])
@pytest.mark.parametrize("tau", [1, 5])
def test_leap_equals_tick_under_sparse_backend(strategy, scenario, tau):
    """Acceptance: the event-leaping stepper stays bit-identical to the
    one-tick oracle when outage pricing runs through the SPARSE hierarchical
    tables, across strategy × {seam outage, multi-cycle eclipse} × τ."""
    if scenario == "seam_detour":
        mesh, wl, ls, ft, wt = CONF_SCENARIOS[scenario](tau)
        fp = None
    else:
        mesh, wl, ls, ft, wt, fp = _conf_second_cycle_wake(tau)
    preshed = ft is not None
    results = {}
    for mode in ("tick", "leap"):
        cfg = simulator.SimConfig(strategy=strategy, capacity=128,
                                  max_ticks=200_000, step_mode=mode,
                                  preshed=preshed,
                                  warn_ticks=2 if preshed else 0)
        results[mode] = simulator.simulate(wl, mesh, cfg, fail_time=ft,
                                           linkstate=ls, wake_time=wt,
                                           fail_period=fp,
                                           routing_backend="sparse")
    a, b = results["tick"], results["leap"]
    for f in EQ_FIELDS:
        assert getattr(a, f) == getattr(b, f), (
            f"{f}: tick={getattr(a, f)} leap={getattr(b, f)}")
    assert (a.per_worker_busy == b.per_worker_busy).all()
    assert (a.per_worker_overflow == b.per_worker_overflow).all()
    assert (a.per_worker_stolen == b.per_worker_stolen).all()


# --------------------------------------------------------------------------- #
# Step counters and the entry points' profiler spans
# --------------------------------------------------------------------------- #
COUNT_ARRIVALS = arrivals.ArrivalConfig(task_cost=5, num_stations=3)


def _count_case(strategy, mode, dynamic):
    """`SimConfig` and `simulate` arguments of one counter case: a static
    mesh, or link state, pre-shed, open-loop arrivals and the recorder."""
    cfg = simulator.SimConfig(strategy=strategy, capacity=128,
                              max_ticks=200_000, step_mode=mode)
    if not dynamic:
        return cfg, {}
    ls, ft = _dynamic_schedule()
    cfg = dataclasses.replace(
        cfg, max_ticks=500, preshed=True, warn_ticks=8,
        arrival_gap_q8=6 * 256, arrival_batch=2,
        trace=tracing.TraceConfig(ring_capacity=4096, bins=16,
                                  bin_ticks=32))
    return cfg, dict(linkstate=ls, fail_time=ft, arrivals=COUNT_ARRIVALS)


def _loop_args(cfg, linkstate=None, fail_time=None, arrivals=None,
               mesh=EQ_MESH):
    """The compiled loop's arguments, as `simulate` prepares them."""
    ft, wt, fp, sp = simulator._fail_speed_arrays(mesh.num_workers,
                                                  fail_time, None)
    return (ft, wt, fp, sp,
            simulator._linkstate_tables(linkstate, mesh, None),
            simulator._arrival_tables(arrivals, mesh))


def _step_counts(cfg, wl=EQ_FIB, mesh=EQ_MESH, **kw):
    scfg, params = cfg.split()
    _, _, ticks, counts = simulator._sim_jit(wl, mesh, scfg, params,
                                             *_loop_args(cfg, mesh=mesh,
                                                         **kw))
    return int(ticks), {k: int(v) for k, v in counts._asdict().items()}


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
@pytest.mark.parametrize("mode", ["tick", "leap"])
@pytest.mark.parametrize("strategy", [stealing.Strategy.NEIGHBOR,
                                      stealing.Strategy.GLOBAL,
                                      stealing.Strategy.ADAPTIVE])
def test_step_counts_split_the_ticks(strategy, mode, dynamic):
    """Every tick is a loop iteration's own tick, a leapt tick or a
    replayed one; the tick stepper leaps and replays none; a replay runs
    at most `famine_batch` ticks; and the iterations are `events`."""
    cfg, kw = _count_case(strategy, mode, dynamic)
    ticks, c = _step_counts(cfg, **kw)
    r = simulator.simulate(EQ_FIB, EQ_MESH, cfg, **kw)
    assert (ticks, c["iterations"]) == (r.ticks, r.events)
    assert c["iterations"] + c["leap_ticks"] + c["famine_ticks"] == ticks
    if mode == "tick":
        assert c["leap_ticks"] == c["famine_ticks"] == 0
        assert c["famine_replays"] == 0
    assert c["famine_ticks"] <= cfg.famine_batch * c["famine_replays"]
    assert c["famine_replays"] <= c["iterations"]


def test_sweep_step_counts_equal_per_point_counts():
    """The vmapped loop freezes a finished point's counters with its
    state: each point of a sweep counts what its own `simulate` counts."""
    cfg, kw = _count_case(stealing.Strategy.NEIGHBOR, "leap", True)
    pts = [cfg.params._replace(strategy=stealing.strategy_code(s), seed=k)
           for k, s in enumerate([stealing.Strategy.NEIGHBOR,
                                  stealing.Strategy.GLOBAL,
                                  stealing.Strategy.ADAPTIVE])]
    B = len(pts)
    ft, wt, fp, sp, ls, ar = _loop_args(cfg, **kw)
    _, _, ticks, counts = simulator._sim_batch_jit(
        EQ_FIB, EQ_MESH, cfg.static, simulator.stack_params(pts),
        *(jnp.broadcast_to(x[None], (B,) + x.shape) for x in (ft, wt, fp,
                                                             sp)), ls, ar)
    for i, p in enumerate(pts):
        one = dataclasses.replace(cfg, strategy=stealing.CODE_STRATEGIES[
            int(p.strategy)], seed=int(p.seed))
        want_ticks, want = _step_counts(one, **kw)
        assert int(ticks[i]) == want_ticks
        assert {k: int(v[i]) for k, v in counts._asdict().items()} == want


def test_sweep_famine_windows_differ_per_point():
    """Points whose famine windows differ — NEIGHBOR and GLOBAL at τ 1 and
    5, and LIFELINE, whose replay predicate is always false — share one
    vmapped replay loop, which runs to the longest lane's window: each
    point still gets exactly its own `simulate`'s `SimResult`, recorder
    ring and time series included, and its own `StepCounts`."""
    import jax

    mesh = topology.MeshTopology.grid(4, 5, torus=True)
    cfg = simulator.SimConfig(
        capacity=64, max_ticks=100_000,
        trace=tracing.TraceConfig(ring_capacity=4096, bins=16,
                                  bin_ticks=64))
    cases = [(stealing.Strategy.NEIGHBOR, 1), (stealing.Strategy.NEIGHBOR, 5),
             (stealing.Strategy.GLOBAL, 1), (stealing.Strategy.GLOBAL, 5),
             (stealing.Strategy.LIFELINE, 5)]
    cfgs = [dataclasses.replace(cfg, strategy=s, hop_ticks=tau, seed=k)
            for k, (s, tau) in enumerate(cases)]
    pts = [c.params for c in cfgs]
    B = len(pts)
    swept = simulator.simulate_sweep(FAMINE_WL, mesh, cfg, pts)
    ft, wt, fp, sp, ls, ar = _loop_args(cfg, mesh=mesh)
    _, _, _, counts = jax.device_get(simulator._sim_batch_jit(
        FAMINE_WL, mesh, cfg.static, simulator.stack_params(pts),
        *(jnp.broadcast_to(x[None], (B,) + x.shape) for x in (ft, wt, fp,
                                                             sp)), ls, ar))
    per_point = []
    for i, one in enumerate(cfgs):
        a, b = swept[i], simulator.simulate(FAMINE_WL, mesh, one)
        for f in EQ_FIELDS + ("events",):
            assert getattr(a, f) == getattr(b, f), (cases[i], f)
        for f in ("per_worker_busy", "per_worker_overflow",
                  "per_worker_stolen", "per_worker_attempts",
                  "per_worker_successes", "per_worker_hiwater"):
            assert (getattr(a, f) == getattr(b, f)).all(), (cases[i], f)
        assert a.trace.emitted == b.trace.emitted > 0, cases[i]
        assert (a.trace.events == b.trace.events).all(), cases[i]
        assert (a.timeseries.data == b.timeseries.data).all(), cases[i]
        assert a.result == FAMINE_WL.expected_result(), cases[i]
        want_ticks, want = _step_counts(one, wl=FAMINE_WL, mesh=mesh)
        got = {k: int(v[i]) for k, v in counts._asdict().items()}
        assert (a.ticks, got) == (want_ticks, want), cases[i]
        per_point.append((got["famine_replays"], got["famine_ticks"]))
    # the lanes really replay different windows, and LIFELINE none
    assert per_point[-1] == (0, 0)
    assert all(r > 0 for r, _ in per_point[:-1]), per_point
    assert len(set(per_point)) > 2, per_point


def test_simulate_span_carries_step_counts(tmp_path):
    """Under the profiler, `simulate` writes its span with the step
    counters as arguments, and its five children nest inside it in
    order."""
    import jax

    cfg, kw = _count_case(stealing.Strategy.NEIGHBOR, "leap", False)
    simulator.simulate(EQ_FIB, EQ_MESH, cfg, **kw)   # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1                       # spans, no CPU ops
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    r = simulator.simulate(EQ_FIB, EQ_MESH, cfg, **kw)
    jax.profiler.stop_trace()
    path, = tmp_path.rglob("*.xplane.pb")
    spans = sorted(
        ([ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
          dict(ev.stats)]
         for plane in jax.profiler.ProfileData.from_file(str(path)).planes
         for line in plane.lines for ev in line.events
         if ev.name.startswith("simulate")), key=lambda s: s[1])
    (_, s0, e0, args), *children = spans
    ticks, counts = _step_counts(cfg, **kw)
    assert args == {"seed": cfg.seed, "workers": EQ_MESH.num_workers,
                    "ticks": ticks, **counts}
    assert args["ticks"] == r.ticks and args["iterations"] == r.events
    assert [c[0] for c in children] == [
        "simulate.prepare", "simulate.dispatch", "simulate.wait",
        "simulate.fetch", "simulate.finalize"]
    ends = [s0] + [c[2] for c in children]
    for (_, s, e, _), prev_end in zip(children, ends):
        assert prev_end <= s <= e <= e0
