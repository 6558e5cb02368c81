"""Compile the switch kernels and the kernel sweep program for a TPU v5e.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
v5e that is described rather than attached, and refuses what the chip would
refuse (a misaligned DMA, a scalar store to vector memory, too much SMEM) —
faults that interpret mode cannot see.  The topology is described inside a
module fixture, never at import, and the file skips where it cannot be
described.
"""

import re
from dataclasses import replace
from math import prod

import jax
import jax.numpy as jnp
import numpy as np
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


# ------------------------------------------------ the filter tables' layout --
ROWS = 40          # testbed.switch5: 5 policies x 8 loads
#: async moves between HBM and VMEM (the layout stays): counted apart
_MOVES = ("copy-start", "copy-done", "slice-start", "slice-done")
_INSTR = re.compile(r"(ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w-]+)\((.*)$")


def _computations(text: str) -> dict[str, list[tuple]]:
    """Each computation of compiled HLO text: its top-level instructions
    as ``(root, name, shape, opcode, rest)``."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (m := _INSTR.match(line.strip())):
            cur.append(m.groups())
    return comps


def _sizes(shape: str) -> list[int]:
    """Element counts of the arrays in a (possibly tuple) shape."""
    return [prod(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", shape)]


def tick_table_ops(text: str, n: int) -> dict[str, list[str]]:
    """The ops of the tick loop's body whose result holds all ``n`` slots
    of the filter tables, sorted into ``relayout`` (copy, reshape or
    transpose, or a fusion whose root is one), ``move`` (an async copy
    between memory spaces, which keeps the layout) and ``compute``.

    The tick loop is the innermost ``while`` that carries the tables; a
    fusion counts as its root op; bitcasts, parameters and tuple elements
    are free and not counted."""
    comps = _computations(text)
    root = {c: op for c, insts in comps.items()
            for is_root, _, _, op, _ in insts if is_root}

    def carries(inst):
        return inst[3] == "while" and n in _sizes(inst[2])

    bodies = [re.search(r"body=%([\w.-]+)", inst[4]).group(1)
              for insts in comps.values() for inst in insts if carries(inst)]
    ticks = [b for b in bodies if not any(carries(i) for i in comps[b])]
    assert len(ticks) == 1, ticks
    out = {"relayout": [], "move": [], "compute": []}
    for _, name, shape, op, rest in comps[ticks[0]]:
        if n not in _sizes(shape) or shape.startswith("(") or op in (
                "bitcast", "parameter", "get-tuple-element", "constant"):
            continue
        if op == "fusion":
            op = root[re.search(r"calls=%([\w.-]+)", rest).group(1)]
        if op in ("copy", "reshape", "transpose"):
            out["relayout"].append(f"{name} {shape} {op}")
        elif op in _MOVES or "ConcatBitcast" in rest:
            out["move"].append(f"{name} {shape} {op}")
        else:
            out["compute"].append(f"{name} {shape} {op}")
    return out


@pytest.mark.parametrize("racks", [1, 4])
def test_tick_keeps_the_filter_tables_in_place(one_chip, racks):
    """The fused sweep program of the testbed cell (and of a 4-rack fabric)
    takes no relayout of the filter tables inside the tick: no copy,
    reshape or transpose has their size, and the only ops over them are
    the recovery wipe's select (abstract params keep it) and the filter's
    scatter."""
    cfg = FleetConfig(n_racks=racks, n_servers=6, n_workers=15, n_clients=2,
                      n_filter_tables=2, n_filter_slots=2 ** 17,
                      n_ticks=1024)
    one = make_params(cfg, 0, 0.5, 0)
    params = jax.tree.map(
        lambda a: _sds(one_chip, (ROWS,) + a.shape, a.dtype), one)
    text = lower(cfg, params,
                 options=EngineOptions(backend="fused")).compile().as_text()
    ops_ = tick_table_ops(text, ROWS * cfg.filter_table_size)
    print(f"{racks} rack(s): {len(ops_['relayout'])} table-sized relayouts "
          f"in the tick body; ops {ops_}")
    assert ops_["relayout"] == []
    assert sorted(o.split()[-1] for o in ops_["compute"]) == [
        "scatter", "select"]


def test_staged_tick_keeps_the_filter_tables_in_place(one_chip):
    """The staged sweep program of the seven-policy testbed grid (the
    coordinator and hedge-timer stages compiled in, 7 policies x 8 loads)
    takes no relayout of the filter tables inside
    the tick either: the hedge timer cancels by REQ_ID in its own wheel
    and reads no table, so the only ops over them stay the recovery
    wipe's select (abstract params keep it) and the filter's scatter."""
    cfg = FleetConfig(n_servers=6, n_workers=15, n_clients=2,
                      n_filter_tables=2, n_filter_slots=2 ** 17,
                      n_ticks=1024).with_policy_stages(["laedge", "hedge"])
    assert cfg.coordinator and cfg.hedge_timer
    one = make_params(cfg, 0, 0.5, 0)
    params = jax.tree.map(
        lambda a: _sds(one_chip, (GRID,) + a.shape, a.dtype), one)
    assert EngineOptions().resolve_backend(cfg) == "staged"
    text = lower(cfg, params).compile().as_text()
    ops_ = tick_table_ops(text, GRID * cfg.filter_table_size)
    print(f"staged: {len(ops_['relayout'])} table-sized relayouts in the "
          f"tick body; ops {ops_}")
    assert ops_["relayout"] == []
    assert sorted(o.split()[-1] for o in ops_["compute"]) == [
        "scatter", "select"]


def _testbed_grid(one_chip, staged: bool, window):
    """The testbed cell's grid for a described v5e: ``switch5``'s fused
    40 rows, or ``all7``'s staged 56 with the coordinator and hedge timer.
    Every input is abstract but the failure windows' recovery ticks, which
    the engine entry reads to decide on the §3.6 wipe."""
    cfg = FleetConfig(n_servers=6, n_workers=15, n_clients=2,
                      n_filter_tables=2, n_filter_slots=2 ** 17,
                      n_ticks=1024)
    rows = ROWS
    if staged:
        cfg, rows = cfg.with_policy_stages(["laedge", "hedge"]), GRID
    one = make_params(cfg, 0, 0.5, 0, fail_window=window)
    params = jax.tree.map(
        lambda a: _sds(one_chip, (rows,) + a.shape, a.dtype), one)
    params = params._replace(
        fail_until_tick=np.full(rows, one.fail_until_tick, np.int32))
    return cfg, params, rows


@pytest.mark.parametrize("window", [None, (100, 200)],
                         ids=["no-window", "window"])
@pytest.mark.parametrize("staged", [False, True], ids=["fused", "staged"])
def test_tick_wipes_the_tables_only_with_a_window(one_chip, staged, window):
    """Without a failure window no switch recovers inside the horizon, so
    the tick holds no select over the filter tables (the §3.6 wipe is not
    compiled in) and only the filter's scatter is table-sized compute; a
    window recovering inside the horizon compiles the wipe's select in."""
    cfg, params, rows = _testbed_grid(one_chip, staged, window)
    opts = EngineOptions(backend="staged" if staged else "fused")
    text = lower(cfg, params, options=opts).compile().as_text()
    ops_ = tick_table_ops(text, rows * cfg.filter_table_size)
    print(f"{opts.backend}, window {window}: ops {ops_}")
    assert ops_["relayout"] == []
    assert sorted(o.split()[-1] for o in ops_["compute"]) == (
        ["scatter"] if window is None else ["scatter", "select"])
