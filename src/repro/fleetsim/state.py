"""Device-resident fleet state: the whole 2-tier fabric as arrays.

One :class:`FleetState` holds everything the DES keeps in Python objects —
per-rack switch soft state (the same layout as ``repro.core.switch_jax``,
stacked over a leading ``n_racks`` axis), a spine tier that assigns
fabric-global REQ_IDs and filters inter-rack clone pairs, per-server FCFS
queues and worker pools, client receiver backlogs, and the running metrics —
so a single ``lax.scan`` step can advance the entire cluster and ``vmap``
can advance thousands of clusters.

Representation choices are driven by what is cheap inside a jitted scan on
any backend (no sorts, few scatters):

* each server's FCFS queue is a **ring buffer**: ``head``/``count`` scalars
  per server plus one stacked ``(R, S, Q, QF)`` payload array, so enqueue and
  dequeue are a handful of gathers/scatters at computed offsets and FCFS
  order is positional — no stamps, no argsort;
* worker metadata is likewise stacked into one ``(R, S, W, WF)`` array so a
  tick writes it with a single scatter;
* rack-structured arrays carry a leading ``n_racks`` axis but the engine
  flattens it away inside the tick, so every per-server op is the same
  single gather/scatter it was for one ToR.

Integer payload fields (req ids, CLO, …) ride in the float32 payload arrays;
``FleetConfig`` bounds req ids below 2²⁴ so the round-trip is exact.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.fleetsim.config import FleetConfig
from repro.fleetsim.telemetry.device import (
    SeriesState,
    TraceBuffer,
    init_series_state,
    init_trace_buffer,
)

# queue payload fields, (R, S, Q, QF) — float32, ints exact below 2^24
QF_BASE = 0     # intrinsic service demand (µs)
QF_TARR = 1     # switch-arrival time (µs)
QF_RID = 2      # REQ_ID
QF_CLO = 3      # CLO marking
QF_IDX = 4      # filter-table index (within one switch's table group)
QF_CLIENT = 5   # client id
QF_HOP = 6      # extra per-copy hop latency (µs; inter-rack clone detour)
QF_FRACK = 7    # filter location: home rack id, or n_racks for the spine
QF = 8

# worker payload fields, (R, S, W, WF).  A worker is busy iff REM > 0, so one
# stacked array (and one scatter per tick) carries the whole pool.
WF_REM = 0      # remaining execution time (µs); 0 ⇔ idle
WF_TARR = 1
WF_RID = 2
WF_CLO = 3
WF_IDX = 4
WF_CLIENT = 5
WF_HOP = 6
WF_FRACK = 7
WF = 8


WHEEL_RID = 0    # timer-wheel entry fields, (n_slots, width, WH) — float32
WHEEL_DST = 1    # deferred duplicate's destination (fabric-global)
WHEEL_IDX = 2    # filter-table index
WHEEL_CLIENT = 3
WHEEL_BASE = 4   # intrinsic demand shared with the original
WHEEL_TARR = 5   # the ORIGINAL arrival time — the hedge pays the delay
WHEEL_FRACK = 6  # filter location (home rack)
WH = 7


class FabricSwitch(NamedTuple):
    """All switch soft state of the 2-tier fabric (wiped on failure, §3.6).

    ``seq`` lives at the spine so REQ_IDs are unique fabric-wide (the client
    dedup table and the filter fingerprints key on REQ_ID alone).  Each rack
    switch tracks only its own rack's piggybacked queue lengths; the spine's
    aggregated per-rack view used for inter-rack placement is derived from
    the same array.  ``filter_tables`` holds the per-rack table groups plus
    one extra group (index ``n_racks``) for the spine, which filters the
    clone pairs whose copies span racks — the only point both responses of
    such a pair traverse.  The groups are laid out group-major as one
    flat vector, slot ``s`` of table ``t`` in group ``g`` (a rack, or
    ``n_racks`` for the spine) at flat index ``i = (g·n_tables + t)·n_slots
    + s``, and carried in rows of ``row = min(128, n_slots)`` slots: at
    ``[i // row, i % row]`` (``FleetConfig.filter_table_shape``).  The
    tick reads and writes that cell and takes no view of another shape: on
    the TPU each shape has its own tiled layout, so a view copies every
    table, while rows of 128 int32 tile in plain row-major order, the
    order the batched scatter's flattened operand needs.
    """

    seq: jax.Array            # () int32 — spine-global REQ_ID sequence
    server_state: jax.Array   # (n_racks, S) int32 — per-rack StateT/ShadowT
    filter_tables: jax.Array  # FleetConfig.filter_table_shape int32


class RingQueues(NamedTuple):
    """Per-server FCFS ring buffers, rack-major."""

    head: jax.Array     # (n_racks, S) int32 — oldest occupied slot
    count: jax.Array    # (n_racks, S) int32 — waiting requests
    data: jax.Array     # (n_racks, S, Q, QF) float32 payload


class Workers(NamedTuple):
    meta: jax.Array     # (n_racks, S, W, WF) float32 payload; busy ⇔ REM > 0


class CoordState(NamedTuple):
    """Array-form coordinator node (LÆDGE, §2.2) — one CPU hanging off the
    top switch, as the DES models it.

    The CPU serves packets FCFS at ``coord_cpu_us`` each: every request it
    receives, every duplicate it sends, every waiting request it later
    sends, and every response.  ``cpu_free`` is the time its queued work
    ends (µs, unbounded: a CPU that receives more than it can serve falls
    further behind every tick).  Decisions are taken in arrival order, as
    the DES takes them; only the packets wait for the CPU.  A dispatched
    copy therefore sits in the ``out_*`` ring (FIFO by the time it reaches
    its server, held in its ``QF_HOP`` field) until that tick, and holds
    its server's ``outstanding`` slot all the while.  ``outstanding`` is
    the coordinator's own dispatched-minus-responded view per server,
    the idleness signal of the LÆDGE rule (idle ⇔ outstanding <
    n_workers); requests that find no idle server wait in the ``head`` /
    ``count`` / ``data`` ring until a response frees one.
    """

    outstanding: jax.Array  # (n_racks · S,) int32
    head: jax.Array         # () int32 — oldest waiting request's slot
    count: jax.Array        # () int32 — waiting requests
    data: jax.Array         # (coordinator_cap, QF) float32 payload rows
    cpu_free: jax.Array     # () float32 — µs at which the CPU is idle
    out_head: jax.Array     # () int32 — oldest copy on its way
    out_count: jax.Array    # () int32 — copies on their way
    out_dst: jax.Array      # (n_racks · S · W,) int32 destination server
    out_data: jax.Array     # (n_racks · S · W, QF) float32; QF_HOP: arrival


class HedgeWheel(NamedTuple):
    """Fixed-depth timer wheel firing delayed hedge duplicates.

    An entry armed at tick ``t`` lands in slot ``(t + delay) % n_slots``
    and fires when the tick counter reaches that slot again — exactly
    ``delay`` ticks later, because the wheel is deeper than the delay
    horizon (enforced by ``FleetConfig``).  Per-slot occupancy beyond
    ``wheel_width`` drops the *latest* lanes deterministically (counted in
    ``Metrics.n_wheel_dropped``).  ``cancelled`` marks the entries whose
    original's response passed its switch before the timer fired — the
    DES's cancel-on-first-response, keyed by REQ_ID.
    """

    count: jax.Array      # (n_slots,) int32 — armed entries per slot
    data: jax.Array       # (n_slots, width, WH) float32 entries
    cancelled: jax.Array  # (n_slots, width) bool


class Metrics(NamedTuple):
    """Running counters + the per-rack log-spaced latency histograms."""

    hist: jax.Array             # (n_racks, hist_bins) int32 — by serving rack
    n_arrivals: jax.Array       # requests admitted at the fabric
    n_truncated: jax.Array      # Poisson arrivals clipped by lane headroom
    n_dropped_down: jax.Array   # arrivals lost while the fabric was dark
    n_cloned: jax.Array
    n_interrack_cloned: jax.Array  # … of which the clone crossed racks
    n_clone_drops: jax.Array    # server-side CLO=2 stale-state drops
    n_filtered: jax.Array       # redundant responses dropped at any switch
    n_spine_filtered: jax.Array  # … of which at the spine (inter-rack pairs)
    n_redundant: jax.Array      # redundant responses absorbed at clients
    n_overflow: jax.Array       # queue-slot exhaustion drops
    n_dedup_evicted: jax.Array  # live client fingerprints lost to collisions
    n_resp_clipped: jax.Array   # completions beyond the response-lane budget
    n_completed: jax.Array      # first responses delivered (whole run)
    n_completed_win: jax.Array  # … finishing inside the measurement window
    n_resp: jax.Array           # all server completions
    n_resp_empty: jax.Array     # … that piggybacked qlen == 0
    lost_down_resp: jax.Array   # responses lost while the fabric was dark
    # staged-pipeline counters (always present; only the coordinator /
    # hedge_timer stages ever move them off zero)
    n_coord_queued: jax.Array   # requests accepted by the coordinator node
    n_coord_overflow: jax.Array  # … lost to waiting-ring exhaustion
    n_coord_pending: jax.Array  # … that waited there for an idle server
    n_coord_packets: jax.Array  # CPU passes (× coord_cpu_us = busy time)
    n_coord_resp_waited: jax.Array  # responses that found the CPU busy
    n_hedges_armed: jax.Array   # timer-wheel entries armed
    # … cancelled by an earlier response, or lost with a dark fabric (the
    # DES likewise silently drops a hedge firing into a down switch)
    n_hedges_cancelled: jax.Array
    n_wheel_dropped: jax.Array  # … lost to wheel-slot exhaustion
    # batch-server occupancy (ServeSim, repro.fleetsim.llmserve): busy
    # decode slots summed over servers × ticks; only the batch server
    # stage ever moves it off zero
    n_slot_busy: jax.Array
    # ChaosFuzz link-failure campaign counters (repro.fleetsim.chaos):
    # copies lost on a dead link, request- and response-side.  Inert runs
    # (no link_failure window) keep both pinned at zero bit-identically.
    n_link_dropped_req: jax.Array
    n_link_dropped_resp: jax.Array


class FleetState(NamedTuple):
    switch: FabricSwitch        # seq / per-rack server_state / filter groups
    dedup: jax.Array            # (n_dedup_slots,) int32 client fingerprints
    queues: RingQueues
    workers: Workers
    client_backlog: jax.Array   # (C,) f32 — receiver-thread work backlog (µs)
    key: jax.Array              # PRNG carry
    metrics: Metrics
    # optional stage sub-states: None unless the matching FleetConfig flag
    # compiled the stage in (None is an empty pytree leaf-set, so flag-off
    # programs carry exactly the state they always did)
    coord: CoordState | None = None
    wheel: HedgeWheel | None = None
    # observability sub-states (FleetScope, repro.fleetsim.telemetry):
    # request-event ring buffer + windowed time-series, gated by the static
    # cfg.telemetry flag the same way — pure observers, never fed back
    trace: TraceBuffer | None = None
    series: SeriesState | None = None


def init_fabric_switch(cfg: FleetConfig) -> FabricSwitch:
    return FabricSwitch(
        seq=jnp.zeros((), jnp.int32),
        server_state=jnp.zeros((cfg.n_racks, cfg.n_servers), jnp.int32),
        filter_tables=jnp.zeros(cfg.filter_table_shape, jnp.int32),
    )


def init_metrics(cfg: FleetConfig) -> Metrics:
    z = jnp.zeros((), jnp.int32)
    return Metrics(hist=jnp.zeros((cfg.n_racks, cfg.hist_bins), jnp.int32),
                   n_arrivals=z, n_truncated=z, n_dropped_down=z,
                   n_cloned=z, n_interrack_cloned=z,
                   n_clone_drops=z, n_filtered=z, n_spine_filtered=z,
                   n_redundant=z,
                   n_overflow=z, n_dedup_evicted=z, n_resp_clipped=z,
                   n_completed=z,
                   n_completed_win=z, n_resp=z, n_resp_empty=z,
                   lost_down_resp=z,
                   n_coord_queued=z, n_coord_overflow=z,
                   n_coord_pending=z, n_coord_packets=z,
                   n_coord_resp_waited=z,
                   n_hedges_armed=z,
                   n_hedges_cancelled=z, n_wheel_dropped=z,
                   n_slot_busy=z,
                   n_link_dropped_req=z, n_link_dropped_resp=z)


def init_coord_state(cfg: FleetConfig) -> CoordState:
    # a copy on its way holds an outstanding slot of its server, and no
    # server takes more than n_workers: that many copies fill the ring
    out = cfg.n_servers_total * cfg.n_workers
    z = jnp.zeros((), jnp.int32)
    return CoordState(
        outstanding=jnp.zeros((cfg.n_servers_total,), jnp.int32),
        head=z, count=z,
        data=jnp.zeros((cfg.coordinator_cap, QF), jnp.float32),
        cpu_free=jnp.zeros((), jnp.float32),
        out_head=z, out_count=z,
        out_dst=jnp.zeros((out,), jnp.int32),
        out_data=jnp.zeros((out, QF), jnp.float32),
    )


def init_hedge_wheel(cfg: FleetConfig) -> HedgeWheel:
    return HedgeWheel(
        count=jnp.zeros((cfg.wheel_slots,), jnp.int32),
        data=jnp.zeros((cfg.wheel_slots, cfg.wheel_width, WH), jnp.float32),
        cancelled=jnp.zeros((cfg.wheel_slots, cfg.wheel_width), bool),
    )


def init_fleet_state(cfg: FleetConfig, key: jax.Array) -> FleetState:
    r, s, q = cfg.n_racks, cfg.n_servers, cfg.queue_cap
    # under server_model="batch" the worker lanes are the decode slots
    # (same WF payload layout, one stacked array, one scatter per tick)
    w = cfg.n_slots if cfg.server_model == "batch" else cfg.n_workers
    return FleetState(
        switch=init_fabric_switch(cfg),
        dedup=jnp.zeros((cfg.n_dedup_slots,), jnp.int32),
        queues=RingQueues(head=jnp.zeros((r, s), jnp.int32),
                          count=jnp.zeros((r, s), jnp.int32),
                          data=jnp.zeros((r, s, q, QF), jnp.float32)),
        workers=Workers(meta=jnp.zeros((r, s, w, WF), jnp.float32)),
        client_backlog=jnp.zeros((cfg.n_clients,), jnp.float32),
        key=key,
        metrics=init_metrics(cfg),
        coord=init_coord_state(cfg) if cfg.coordinator else None,
        wheel=init_hedge_wheel(cfg) if cfg.hedge_timer else None,
        trace=init_trace_buffer(cfg) if cfg.telemetry else None,
        series=init_series_state(cfg) if cfg.telemetry else None,
    )
