"""Vectorized JAX form of the NetClone data plane — the TPU-native rethink.

A Tofino pipeline amortises the cloning decision over pipeline *stages*; a
TPU amortises it over vector *lanes*.  One jitted "dispatch tick" makes
cloning decisions for a whole batch of requests, and one "filter tick"
processes a whole batch of responses against the fingerprint tables, with
semantics identical to processing the packets one at a time in arrival order
(verified against :class:`repro.core.switch.NetCloneSwitch` in tests).

State is carried functionally in :class:`SwitchState`; the request path never
writes the state table (faithful to Algorithm 1 — only responses update
server state, which is what produces the paper's herding behaviour at high
load and its server-side CLO=2 drop rule).

The response filter has two implementations:

* ``filter_tick``         — lax.scan reference (exact sequential semantics);
* ``kernels.fingerprint_filter`` — the Pallas kernel with the tables resident
  in VMEM (used by the serving dispatcher; same semantics, one kernel launch).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tables import GroupTable

_HASH_MULT = jnp.uint32(2654435761)


def fingerprint_hash_jax(req_id: jax.Array, n_slots: int) -> jax.Array:
    """Same multiplicative hash as ``repro.core.tables.fingerprint_hash``."""
    x = (req_id.astype(jnp.uint32) * _HASH_MULT) >> jnp.uint32(15)
    return (x % jnp.uint32(n_slots)).astype(jnp.int32)


class SwitchState(NamedTuple):
    """All switch soft state (wiped on failure, §3.6)."""

    seq: jax.Array           # () int32 — global REQ_ID sequence
    server_state: jax.Array  # (n_servers,) int32 — piggybacked queue lengths
    filter_tables: jax.Array # (n_tables, n_slots) int32 — fingerprints


def init_switch_state(n_servers: int, n_tables: int = 2,
                      n_slots: int = 2 ** 12) -> SwitchState:
    return SwitchState(
        seq=jnp.zeros((), jnp.int32),
        server_state=jnp.zeros((n_servers,), jnp.int32),
        filter_tables=jnp.zeros((n_tables, n_slots), jnp.int32),
    )


def group_pairs_array(n_servers: int) -> jax.Array:
    """GrpT as a device array: (2·C(n,2), 2) int32."""
    return jnp.asarray(GroupTable(n_servers).pairs)


class DispatchResult(NamedTuple):
    req_id: jax.Array   # (B,) int32
    dst1: jax.Array     # (B,) int32 — always receives the CLO∈{0,1} copy
    dst2: jax.Array     # (B,) int32 — receives the CLO=2 clone when cloned
    cloned: jax.Array   # (B,) bool


@functools.partial(jax.jit, static_argnames=())
def dispatch_tick(state: SwitchState, group_pairs: jax.Array,
                  grp: jax.Array) -> tuple[SwitchState, DispatchResult]:
    """Request path (Alg. 1 lines 1-13) for a batch of B requests.

    The cloning predicate reads the state table as of the start of the tick
    for every lane — exactly what B back-to-back pipeline passes see, since
    requests never write ``server_state``.
    """
    b = grp.shape[0]
    req_id = state.seq + 1 + jnp.arange(b, dtype=jnp.int32)
    pair = group_pairs[grp]                       # (B, 2)
    s1, s2 = pair[:, 0], pair[:, 1]
    idle1 = state.server_state[s1] == 0           # StateT read
    idle2 = state.server_state[s2] == 0           # ShadowT read (same values)
    cloned = idle1 & idle2
    new_state = state._replace(seq=state.seq + jnp.int32(b))
    return new_state, DispatchResult(req_id=req_id, dst1=s1, dst2=s2,
                                     cloned=cloned)


class FilterResult(NamedTuple):
    drop: jax.Array  # (B,) bool — redundant slower responses to suppress


def _filter_step(tables, resp):
    req_id, idx, clo = resp
    n_slots = tables.shape[1]
    slot = fingerprint_hash_jax(req_id, n_slots)
    occupant = tables[idx, slot]
    is_cloned = clo > 0
    hit = is_cloned & (occupant == req_id)
    # hit  → clear slot, drop response; miss → insert fingerprint (overwrite)
    new_val = jnp.where(hit, jnp.int32(0), req_id)
    tables = jax.lax.cond(
        is_cloned,
        lambda tb: tb.at[idx, slot].set(new_val),
        lambda tb: tb,
        tables,
    )
    return tables, hit


@jax.jit
def filter_tick(state: SwitchState, req_id: jax.Array, idx: jax.Array,
                clo: jax.Array, sid: jax.Array,
                qlen: jax.Array) -> tuple[SwitchState, FilterResult]:
    """Response path (Alg. 1 lines 14-26) for a batch of B responses,
    processed in lane order (sequential semantics — two responses of the same
    request in one tick behave exactly as in the switch)."""
    # lines 15-16: last write wins per server, in lane order
    server_state = state.server_state.at[sid].set(qlen)
    tables, drop = jax.lax.scan(
        _filter_step, state.filter_tables,
        (req_id.astype(jnp.int32), idx.astype(jnp.int32), clo.astype(jnp.int32)),
    )
    new_state = state._replace(server_state=server_state, filter_tables=tables)
    return new_state, FilterResult(drop=drop)


@jax.jit
def filter_tick_vectorized(state: SwitchState, req_id: jax.Array,
                           idx: jax.Array, clo: jax.Array, sid: jax.Array,
                           qlen: jax.Array,
                           active: jax.Array | None = None,
                           ) -> tuple[SwitchState, FilterResult]:
    """One-scatter form of :func:`filter_tick` for fleet-scale ticks.

    ``filter_tick`` replays lanes sequentially (a B-step ``lax.scan``);
    inside a time-stepped fleet simulation that inner scan dominates runtime.
    This variant resolves a whole tick with O(B²) lane comparisons + one
    scatter.  Lanes sharing one (req_id, idx) key alternate hit/insert against
    the slot exactly as the sequential filter does (a parked fingerprint makes
    the group's first lane the hit; otherwise the second), for any group size.
    The single knowable divergence is a *different-id* slot collision within
    one tick (an unrelated insert landing between a parked fingerprint and its
    owner's response in the same tick): the response is dropped here where the
    sequential filter would forward it — the client-side dedup absorbs either
    outcome.  ``active`` masks padding lanes.
    """
    if active is None:
        active = jnp.ones(req_id.shape, bool)
    n_slots = state.filter_tables.shape[1]

    # lines 15-16: last write wins per server, in lane order (masked lanes
    # scatter out of bounds and are dropped)
    sid_m = jnp.where(active, sid.astype(jnp.int32),
                      jnp.int32(state.server_state.shape[0]))
    server_state = state.server_state.at[sid_m].set(
        qlen.astype(jnp.int32), mode="drop")

    tables, drop = filter_rows(state.filter_tables, n_slots, req_id, idx, clo,
                               active)
    new_state = state._replace(server_state=server_state, filter_tables=tables)
    return new_state, FilterResult(drop=drop)


def slot_cell(idx: jax.Array, slot: jax.Array, n_slots: int,
              row_len: int) -> tuple[jax.Array, jax.Array]:
    """Row and column of table ``idx``'s ``slot`` in a table stack laid out
    row-major in rows of ``row_len`` slots: flat index
    ``idx·n_slots + slot``.  ``row_len == n_slots`` is the plain
    ``(n_tables, n_slots)`` stack."""
    at = idx * n_slots + slot
    return at // row_len, at % row_len


def filter_rows(tables: jax.Array, n_slots: int, req_id: jax.Array,
                idx: jax.Array, clo: jax.Array, active: jax.Array,
                ) -> tuple[jax.Array, jax.Array]:
    """The fingerprint filter of :func:`filter_tick_vectorized` over a
    table stack of ``n_slots``-slot tables laid out row-major in rows of
    ``tables.shape[1]`` slots, a divisor of ``n_slots`` (:func:`slot_cell`).

    The layout changes which cell a lane reads and writes, never the
    lanes' drops or the tables' contents.  Returns ``(tables, drop)``;
    masked or CLO=0 lanes write one row past the end and are dropped.
    """
    req_id = req_id.astype(jnp.int32)
    idx = idx.astype(jnp.int32)
    part = active & (clo > 0)                     # lanes touching FilterT
    row, col = slot_cell(idx, fingerprint_hash_jax(req_id, n_slots), n_slots,
                         tables.shape[1])
    parked = tables[row, col] == req_id           # fingerprint already there
    lane = jnp.arange(req_id.shape[0])
    same = (part[:, None] & part[None, :]
            & (req_id[:, None] == req_id[None, :])
            & (idx[:, None] == idx[None, :]))
    k = jnp.sum(same & (lane[None, :] < lane[:, None]), axis=1)  # group pos
    n = jnp.sum(same, axis=1)                                    # group size
    # sequential replay of a key group alternates hit/insert starting from
    # the parked state: lane at even position drops iff parked, odd iff not
    drop = part & jnp.where(k % 2 == 0, parked, ~parked)
    # slot value after the whole group: parked0 XOR (group size odd)
    parked_final = jnp.where(n % 2 == 0, parked, ~parked)
    value = jnp.where(parked_final, req_id, jnp.int32(0))
    row_m = jnp.where(part, row, jnp.int32(tables.shape[0]))
    return tables.at[row_m, col].set(value, mode="drop"), drop


@jax.jit
def wipe(state: SwitchState) -> SwitchState:
    """Switch failure: lose all soft state (§3.6)."""
    return SwitchState(
        seq=jnp.zeros_like(state.seq),
        server_state=jnp.zeros_like(state.server_state),
        filter_tables=jnp.zeros_like(state.filter_tables),
    )


# ----------------------------------------------------------------------------
# Numpy oracle used by property tests (mirrors NetCloneSwitch exactly but
# over batches, so it can be compared element-wise with the jitted ticks).
# ----------------------------------------------------------------------------
def dispatch_tick_oracle(seq: int, server_state: np.ndarray,
                         group_pairs: np.ndarray, grp: np.ndarray):
    req_id = seq + 1 + np.arange(len(grp), dtype=np.int64)
    s1 = group_pairs[grp, 0]
    s2 = group_pairs[grp, 1]
    cloned = (server_state[s1] == 0) & (server_state[s2] == 0)
    return seq + len(grp), req_id, s1, s2, cloned


def filter_tick_oracle(tables: np.ndarray, server_state: np.ndarray,
                       req_id, idx, clo, sid, qlen):
    tables = tables.copy()
    server_state = server_state.copy()
    drop = np.zeros(len(req_id), dtype=bool)
    n_slots = tables.shape[1]
    for k in range(len(req_id)):
        server_state[sid[k]] = qlen[k]
        if clo[k] > 0:
            x = (np.uint64(req_id[k]) * np.uint64(2654435761)) & np.uint64(0xFFFFFFFF)
            slot = int((x >> np.uint64(15)) % np.uint64(n_slots))
            if tables[idx[k], slot] == req_id[k]:
                tables[idx[k], slot] = 0
                drop[k] = True
            else:
                tables[idx[k], slot] = req_id[k]
    return tables, server_state, drop
