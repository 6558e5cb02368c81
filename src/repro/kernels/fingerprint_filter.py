"""Pallas TPU kernel: NetClone fingerprint filter (paper §3.5).

The switch keeps its filter tables in register arrays updated at line rate;
the TPU analogue processes a whole batch of responses per kernel launch.
Semantics are *sequential in lane order* — identical to packets traversing
the pipeline one after another — which is why the update loop is a
``fori_loop`` over the batch rather than a vectorized scatter (two responses
of the same request in one batch must see each other's writes).

This is the TickFuse kernel (:mod:`repro.kernels.tickfuse`) with no StateT:
each launch DMAs the tables from HBM into SMEM scratch (the only TPU memory
with scalar stores), runs the lane loop and DMAs them back.  The budget is
:func:`~repro.kernels.tickfuse.smem_bytes` against
:data:`~repro.kernels.tickfuse.SMEM_LIMIT_BYTES` (1 MiB less one 4 KiB
tile, what the v5e compiler accepts): the serving cluster's 2×4096 slots
(padded to 8 rows) take 136 KiB; the prototype's 2×2¹⁷ slots do not fit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.tickfuse import switch_response


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fingerprint_filter(
    tables: jax.Array,   # (n_tables, n_slots) int32 — resident filter state
    req_id: jax.Array,   # (B,) int32
    idx: jax.Array,      # (B,) int32  filter-table index (IDX field)
    clo: jax.Array,      # (B,) int32  CLO field (0 → pass-through)
    *,
    block: int = 256,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Returns ``(new_tables, drop)`` with exact switch semantics.

    The lanes are padded to a multiple of ``block`` (CLO=0 padding never
    touches the tables), so callers with a varying batch size reuse a few
    compiled shapes."""
    b = req_id.shape[0]
    lanes = [jnp.pad(a.astype(jnp.int32), (0, -b % block))
             for a in (req_id, idx, clo)]
    # no StateT: zero servers, so no sid passes the `sid < n_servers` guard
    zeros = jnp.zeros_like(lanes[0])
    _, new_tables, drop = switch_response(
        zeros[:0], tables, *lanes, zeros, zeros, interpret=interpret)
    return new_tables, drop[:b].astype(bool)
