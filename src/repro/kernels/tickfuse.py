"""Pallas TPU kernel: the fused FleetSim switch response path (TickFuse).

One tick's switch response path is two state updates over two resident
tables — the per-server StateT write (piggybacked queue length) and the
fingerprint-filter lookup/insert (paper §3.5) — which the staged engine
issues as a masked XLA scatter followed by a separate
``kernels.fingerprint_filter`` launch.  This kernel fuses them: one
sequential pass over the response lanes performs the StateT write and the
filter decision per lane — exactly the order a response traverses the real
switch pipeline.

Semantics are *sequential in lane order*, identical to
``repro.core.switch_jax._filter_step``: two responses of the same request in
one batch must see each other's table writes (the second is the redundant
one and gets dropped), which is why the body is a ``fori_loop`` rather than
a vectorized scatter.

Layout.  Every lane reads and writes single entries at data-dependent
indices, which the TPU can only do in its scalar memory (SMEM): vector
memory has no scalar stores.  So both operands stay in HBM (``pl.ANY``,
updated in place) and each invocation DMAs one fabric's slice into SMEM
scratch, runs the lane loop there, and DMAs it back.  The operands are
padded to whole ``(8, 128)`` tiles, the unit a DMA slice must align to:

* the *lane block* ``(8, W)`` carries one row per lane field (req_id, idx,
  clo, sid, qlen), the drop mask the kernel writes, and the StateT vector;
* the *table stack* ``(T, M)`` is FilterT, every rack's filter group plus
  the spine's.

A leading fabric axis is the kernel grid: ``jax.vmap`` (the sweep engine's
batching) lands on it through a ``custom_vmap`` rule, so a whole sweep grid
is one launch per tick.

Memory budget: the SMEM scratch is :func:`smem_bytes`, dominated by the
padded table stack (``(n_racks+1)·n_tables × n_slots × 4 B``).  It must fit
:data:`SMEM_LIMIT_BYTES`, the most the v5e compiler accepts (1 MiB less one
4 KiB tile).  With the default 2×2¹⁰-slot tables one rack takes 36 KiB,
four racks 68 KiB and a 64-rack pod 556 KiB; 123 racks is the largest
fabric that fits.  The prototype's 2×2¹⁷-slot tables (4 MiB once padded to
8 rows) do not fit, and ``FleetConfig`` refuses such a config up front
instead of failing inside the compiler.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HASH_MULT = 2654435761

#: SMEM scratch one kernel invocation may allocate.  The v5e has 1 MiB per
#: TensorCore and the compiler keeps 144 B of it, so a full 1 MiB of scratch
#: is refused; scratch comes in whole 4 KiB tiles, so this is the largest
#: size that compiles (tests/test_tpu_compile.py pins both sides)
SMEM_LIMIT_BYTES = (1 << 20) - 4096

# lane-block rows: the five lane fields, the drop mask, the StateT vector
RID, IDX, CLO, SID, QLEN, DROP, STATE = range(7)
_ROWS = 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _block_width(n_lanes: int, n_servers: int) -> int:
    return _round_up(max(n_lanes, n_servers, 1), 128)


def smem_bytes(n_tables: int, n_slots: int, n_servers: int,
               n_lanes: int) -> int:
    """SMEM scratch one invocation allocates: the tile-padded table stack
    plus the lane block."""
    return 4 * (_round_up(n_tables, 8) * _round_up(n_slots, 128)
                + _ROWS * _block_width(n_lanes, n_servers))


@functools.lru_cache(maxsize=None)
def _switch_call(n_lanes: int, n_servers: int, n_slots: int,
                 interpret: bool):
    """``f(block, tables) -> (block, tables)`` over any leading fabric axes;
    ``vmap`` adds one through the ``custom_vmap`` rule."""

    def kernel(block_hbm, tables_hbm, block_out, tables_out, block, tables):
        f = pl.program_id(0)
        pltpu.sync_copy(block_hbm.at[f], block)
        pltpu.sync_copy(tables_hbm.at[f], tables)

        def lane(i, carry):
            # inactive lanes ride in pre-neutralised: sid == n_servers
            # (StateT untouched) and clo == 0 (filter untouched)
            sid = block[SID, i]

            @pl.when(sid < n_servers)
            def _():
                block[STATE, sid] = block[QLEN, i]

            rid, idx, clo = block[RID, i], block[IDX, i], block[CLO, i]
            # multiplicative fingerprint hash (matches repro.core.tables)
            x = (rid.astype(jnp.uint32) * jnp.uint32(_HASH_MULT)) \
                >> jnp.uint32(15)
            slot = (x % jnp.uint32(n_slots)).astype(jnp.int32)
            hit = (clo > 0) & (tables[idx, slot] == rid)

            # hit  → clear the slot and drop the (slower) response
            # miss → insert/overwrite the fingerprint and forward
            @pl.when(clo > 0)
            def _():
                tables[idx, slot] = jnp.where(hit, jnp.int32(0), rid)

            block[DROP, i] = hit.astype(jnp.int32)
            return carry

        jax.lax.fori_loop(0, n_lanes, lane, 0)
        pltpu.sync_copy(block, block_out.at[f])
        pltpu.sync_copy(tables, tables_out.at[f])

    def launch(block, tables):
        hbm = pl.BlockSpec(memory_space=pl.ANY)
        return pl.pallas_call(
            kernel,
            grid=(block.shape[0],),
            in_specs=[hbm, hbm],
            out_specs=[hbm, hbm],
            out_shape=[jax.ShapeDtypeStruct(block.shape, jnp.int32),
                       jax.ShapeDtypeStruct(tables.shape, jnp.int32)],
            scratch_shapes=[pltpu.SMEM(block.shape[1:], jnp.int32),
                            pltpu.SMEM(tables.shape[1:], jnp.int32)],
            input_output_aliases={0: 0, 1: 1},
            interpret=interpret,
        )(block, tables)

    @jax.custom_batching.custom_vmap
    def f(block, tables):
        lead = block.shape[:-2]
        b, t = launch(block.reshape((-1,) + block.shape[-2:]),
                      tables.reshape((-1,) + tables.shape[-2:]))
        return b.reshape(lead + b.shape[1:]), t.reshape(lead + t.shape[1:])

    @f.def_vmap
    def _(axis_size, in_batched, block, tables):
        args = [a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, batched in zip((block, tables), in_batched)]
        return f(*args), (True, True)

    return f


def switch_response(server_state, tables, req_id, idx, clo, sid, qlen, *,
                    interpret: bool):
    """Run the lanes through StateT + FilterT in lane order.

    ``server_state`` is ``(..., S)`` (``S == 0``: no StateT), ``tables``
    ``(..., T, M)`` and the lane fields ``(..., L)``; returns
    ``(server_state, tables, drop)`` with ``drop`` ``(..., L)`` int32."""
    n_lanes, n_servers = req_id.shape[-1], server_state.shape[-1]
    n_tab, n_slots = tables.shape[-2:]
    w = _block_width(n_lanes, n_servers)

    def row(a):
        a = a.astype(jnp.int32)
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, w - a.shape[-1])])

    fields = [row(a) for a in (req_id, idx, clo, sid, qlen)]
    zero = jnp.zeros_like(fields[0])
    block = jnp.stack(fields + [zero, row(server_state), zero], axis=-2)
    pad = [(0, 0)] * (tables.ndim - 2) + [
        (0, _round_up(n_tab, 8) - n_tab), (0, _round_up(n_slots, 128) - n_slots)]
    block, padded = _switch_call(n_lanes, n_servers, n_slots, interpret)(
        block, jnp.pad(tables.astype(jnp.int32), pad))
    return (block[..., STATE, :n_servers], padded[..., :n_tab, :n_slots],
            block[..., DROP, :n_lanes])


@functools.partial(jax.jit, static_argnames=("interpret",))
def tickfuse_response_path(
    server_state: jax.Array,  # (n_servers,) int32 — flat StateT (resident)
    tables: jax.Array,        # (n_tables, n_slots) int32 — FilterT (resident)
    req_id: jax.Array,        # (B,) int32
    idx: jax.Array,           # (B,) int32 — pre-offset filter-table index
    clo: jax.Array,           # (B,) int32 — CLO field (0 → pass-through)
    sid: jax.Array,           # (B,) int32 — responding server (n_servers →
                              # inactive lane, StateT untouched)
    qlen: jax.Array,          # (B,) int32 — piggybacked queue length
    *,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns ``(new_server_state, new_tables, drop)`` with exact
    lane-sequential switch semantics (StateT write, then filter, per lane).

    Inactive lanes must arrive neutralised — ``sid == n_servers`` and
    ``clo == 0`` — the same convention the staged ``_filter_responses``
    scatter path uses."""
    st, tb, drop = switch_response(server_state, tables, req_id, idx, clo,
                                   sid, qlen, interpret=interpret)
    return st, tb, drop.astype(bool)
