"""Compile the switch kernels and the kernel sweep program for a TPU v5e.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
v5e that is described rather than attached, and refuses what the chip would
refuse (a misaligned DMA, a scalar store to vector memory, too much SMEM) —
faults that interpret mode cannot see.  The topology is described inside a
module fixture, never at import, and the file skips where it cannot be
described.
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from repro.fleetsim import EngineOptions, FleetConfig, lower, make_params
from repro.kernels import ops
from repro.kernels.fingerprint_filter import fingerprint_filter
from repro.kernels.tickfuse import (
    SMEM_LIMIT_BYTES,
    smem_bytes,
    tickfuse_response_path,
)

GRID = 56          # the paper sweep: 7 policies x 8 loads
LANES = 32         # FleetConfig.max_responses
SLOTS = 2 ** 10    # FleetConfig.n_filter_slots


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tickfuse(*args):
    return tickfuse_response_path(*args, interpret=False)


def _filter(*args):
    return fingerprint_filter(*args, interpret=False)


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("batch", [None, GRID], ids=["once", "vmapped"])
@pytest.mark.parametrize("racks", [1, 4])
def test_tickfuse_compiles(one_chip, racks, batch):
    lead = () if batch is None else (batch,)
    fn = _tickfuse if batch is None else jax.vmap(_tickfuse)
    args = [_sds(one_chip, lead + (6 * racks,)),
            _sds(one_chip, lead + (2 * (racks + 1), SLOTS))] \
        + [_sds(one_chip, lead + (LANES,))] * 5
    assert "tpu_custom_call" in _compiled_text(fn, args)


@pytest.mark.parametrize("batch", [None, GRID], ids=["once", "vmapped"])
@pytest.mark.parametrize("racks", [1, 4])
def test_fingerprint_filter_compiles(one_chip, racks, batch):
    lead = () if batch is None else (batch,)
    fn = _filter if batch is None else jax.vmap(_filter)
    args = [_sds(one_chip, lead + (2 * (racks + 1), SLOTS))] \
        + [_sds(one_chip, lead + (LANES,))] * 3
    assert "tpu_custom_call" in _compiled_text(fn, args)


def test_smem_limit_is_the_compilers(one_chip):
    """SMEM_LIMIT_BYTES is exactly the largest scratch the compiler takes:
    a kernel at the limit compiles, one 4 KiB tile more is refused."""
    def args(lanes):
        return [_sds(one_chip, (6,)), _sds(one_chip, (248, SLOTS))] \
            + [_sds(one_chip, (lanes,))] * 5

    assert smem_bytes(248, SLOTS, 6, 896) == SMEM_LIMIT_BYTES
    assert "tpu_custom_call" in _compiled_text(_tickfuse, args(896))
    assert smem_bytes(248, SLOTS, 6, 1024) == SMEM_LIMIT_BYTES + 4096
    with pytest.raises(Exception, match="smem"):
        _compiled_text(_tickfuse, args(1024))


def test_fused_tickfuse_sweep_program_compiles(one_chip, monkeypatch):
    """The whole fused 4-rack sweep program with the TickFuse kernel, as
    the engine builds it for a 56-config grid on one chip."""
    # the engine asks the process's backend (CPU here) whether to interpret
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg = FleetConfig(n_racks=4, filter_backend="tickfuse")
    one = make_params(cfg, 0, 0.5, 0)
    params = jax.tree.map(
        lambda a: _sds(one_chip, (GRID,) + a.shape, a.dtype), one)
    compiled = lower(cfg, params,
                     options=EngineOptions(backend="fused")).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the vectorized filter path of the same program has no kernel
    vec = lower(replace(cfg, filter_backend="vectorized"), params,
                options=EngineOptions(backend="fused")).compile()
    assert "tpu_custom_call" not in vec.as_text()
