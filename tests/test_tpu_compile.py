"""Compile the TPU default path for a described v5e chip, with no chip.

The deque kernels (`steal_compact`, `deque_apply`) must lower through Mosaic
at real widths, and one whole simulator step with the staged backend and
both kernels must compile with both kernels in it as `tpu_custom_call`s.
Interpret mode (what every other test runs) cannot show this: it accepts
blocks and ops the chip's compiler refuses.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and several test workers import
this file.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import deque as dq
from repro.core import simulator, stealing, tasks, topology
from repro.kernels import deque_apply, ops, steal_compact

T = dq.TASK_WIDTH
LANES = tasks.EXPAND_K + 1  # the no-recovery push log


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache(topo):
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
            for s in shapes]


KERNELS = {
    "steal_compact": (steal_compact.steal_compact,
                      lambda W, C: [(W, C, T), (W,), (W,), (W,)]),
    "deque_apply": (deque_apply.deque_apply,
                    lambda W, C: [(W, C, T), (W, LANES), (W, LANES, T),
                                  (W,)]),
}


@pytest.mark.parametrize("C", [64, 1024])
@pytest.mark.parametrize("W", [100, 4096])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, W, C, one_chip, no_persistent_cache):
    fn, shapes = KERNELS[kernel]
    compiled = jax.jit(functools.partial(fn, interpret=False)).lower(
        *_shapes(one_chip, *shapes(W, C))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sim_step_compiles_for_v5e_with_both_kernels(one_chip,
                                                     no_persistent_cache):
    """The whole `_sim_jit` program of the TPU default path (staged backend,
    kernels on) compiles for the chip, and both kernels are in it."""
    W = 100
    mesh = topology.MeshTopology.square(W)
    cfg = simulator.SimConfig(strategy=stealing.Strategy.NEIGHBOR,
                              capacity=64, max_ticks=1500,
                              deque_backend="staged", use_steal_kernel=True)
    scfg, params = cfg.split()
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    params = jax.tree.map(lambda _: scalar, params)
    per_worker = _shapes(one_chip, *[(W,)] * 4)
    wl = tasks.FibWorkload(n=30, cutoff=16, max_leaf_cost=64)
    # `ops._interpret` sees this process's CPU backend; the kernels must be
    # traced for Mosaic instead. Traced jaxprs are cached per shape, so the
    # caches are cleared on both sides of the compiled-kernel trace.
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        jax.clear_caches()
        try:
            compiled = simulator._sim_jit.lower(
                wl, mesh, scfg, params, *per_worker, None, None).compile()
        finally:
            jax.clear_caches()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    names = {name for name in ("steal_compact", "deque_apply")
             if any(name in ln for ln in calls)}
    assert names == {"steal_compact", "deque_apply"}, calls
    # the chip's compiler keeps the loop's scopes in each op's metadata,
    # which is how a device trace's op names map to the loop's layers
    scopes = {part for path in re.findall(r'op_name="([^"]*)"', hlo)
              for part in path.split("/")
              if re.fullmatch(r"(stepper|tick)\.\w+", part)}
    assert scopes == {"stepper.tick", "tick.grant", "tick.commit",
                      "stepper.leap", "stepper.famine"}
