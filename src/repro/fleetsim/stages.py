"""The staged tick pipeline: FleetSim's tick as composable pure stages.

One engine tick is the composition

    arrival → route (ToR + spine) → coordinator → hedge_timer
            → server → response/filter → client

where every stage is a pure function ``(cfg, params, state, ctx) ->
(state, ctx)`` over the same :class:`~repro.fleetsim.state.FleetState` the
monolithic step used to carry — the refactor moves code, not semantics.
Stages communicate through two small typed contexts:

* :class:`Arrivals` — this tick's admitted arrival lanes and their
  pre-drawn attributes (candidates, service demand, filter index, …), plus
  the flattened fabric views every later stage reads;
* :class:`Lanes` — the delivery lanes headed for the servers: destination,
  activity mask, and the full ``QF``-format queue payload per lane.  The
  route stage emits ``2 × max_arrivals`` base lanes (originals then
  clones); the coordinator and hedge stages *append* their dispatches.

Two stages are **compile-time optional**, gated by static
:class:`~repro.fleetsim.config.FleetConfig` flags rather than runtime
branches, so a flag-off program contains zero ops from them and the
``n_racks == 1`` goldens of the always-on policies stay bit-identical:

* ``stage_coordinator`` (``cfg.coordinator``) — the LÆDGE coordinator
  node, one FCFS CPU as the DES models it: arrival lanes of policies
  registered with a ``coordinator`` hook are received (one
  ``coord_cpu_us`` pass each) and dispatched by the hook's rule (clone to
  two random idle servers iff ≥ 2 are idle, forward to one when exactly
  one is, wait in a ring otherwise); each copy leaves when the CPU has
  served it, and ``stage_coordinator_responses`` charges every response a
  pass and sends a waiting request onto the slot it frees;
* ``stage_hedge_timer`` (``cfg.hedge_timer``) — a fixed-depth timer wheel:
  policies registered with a ``hedge_timer`` hook arm a deferred duplicate
  at arrival; one hedge delay later the wheel fires it as a CLO=2 copy
  unless ``stage_hedge_cancel`` marked the entry when the original's
  response passed its switch (the DES's cancel-on-first-response).  The
  delay itself is a *traced* per-run input
  (``RunParams.hedge_delay_ticks``, defaulting to the static
  ``cfg.hedge_delay_us``), so a single vmapped — or mesh-sharded, see
  ``repro.fleetsim.shard`` — program sweeps the delay/load plane; only
  the wheel's depth stays compile-time static and must cover the largest
  swept delay (``FleetConfig.with_hedge_horizon``).

Both sub-states live in ``FleetState.coord`` / ``FleetState.wheel`` and are
``None`` when their stage is compiled out.  Policy-specific behaviour
enters exclusively through the unified registry
(``repro.scenarios.registry``): the route branch table, the coordinator
dispatch rules, and the hedge destinations are all ``lax.switch`` tables
built from it at trace time — registering a policy with the right hooks is
the whole integration.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.header import CLO_CLONE, CLO_ORIG
from repro.core.switch_jax import _filter_step, filter_rows
from repro.fleetsim.config import (
    SERVICE_BIMODAL,
    SERVICE_EXPONENTIAL,
    SERVICE_LLM,
    SERVICE_PARETO,
    FleetConfig,
)
from repro.fleetsim.chaos import (
    link_dead,
    stage_link_failure,
    stage_link_response,
)
from repro.fleetsim.policies import dedup_tick, id_mask, route_fabric
from repro.fleetsim.state import (
    QF,
    QF_BASE,
    QF_CLIENT,
    QF_CLO,
    QF_FRACK,
    QF_HOP,
    QF_IDX,
    QF_RID,
    QF_TARR,
    WF,
    WF_CLIENT,
    WF_CLO,
    WF_FRACK,
    WF_HOP,
    WF_IDX,
    WF_REM,
    WF_RID,
    WF_TARR,
    WH,
    WHEEL_BASE,
    WHEEL_CLIENT,
    WHEEL_DST,
    WHEEL_FRACK,
    WHEEL_IDX,
    WHEEL_RID,
    WHEEL_TARR,
    FleetState,
    HedgeWheel,
)
from repro.fleetsim.telemetry.device import emit, series_record_hist, \
    series_tick
from repro.fleetsim.telemetry.events import (
    CLONE_SRC_COORD,
    CLONE_SRC_HEDGE,
    CLONE_SRC_INTERRACK,
    CLONE_SRC_LOCAL,
    EV_ARRIVAL,
    EV_CLIENT_COMPLETE,
    EV_CLIENT_REDUNDANT,
    EV_CLONE,
    EV_COORD_DISPATCH,
    EV_COORD_ENQ,
    EV_COORD_RESP,
    EV_FILTER_DROP,
    EV_HEDGE_ARMED,
    EV_HEDGE_CANCELLED,
    EV_ROUTE,
    EV_SERVER_FINISH,
    EV_SERVER_START,
)
from repro.scenarios import registry


# --------------------------------------------------------------- sampling ---
def _intrinsic(cfg: FleetConfig, u):
    """Per-request base demand (shared by both copies of a clone pair),
    from a pre-drawn uniform in [0, 1)."""
    p = cfg.service.params
    if cfg.service.kind == SERVICE_EXPONENTIAL:
        return jnp.full(u.shape, p[0], jnp.float32)
    if cfg.service.kind == SERVICE_BIMODAL:
        short, long, p_long = p
        return jnp.where(u < p_long, long, short).astype(jnp.float32)
    if cfg.service.kind == SERVICE_PARETO:
        xm, alpha, cap = p
        u = jnp.minimum(u, 1.0 - 1e-7)
        r = (xm / cap) ** alpha
        return (xm / (1.0 - u * (1.0 - r)) ** (1.0 / alpha)).astype(jnp.float32)
    if cfg.service.kind == SERVICE_LLM:
        # prefill + generated-length × per-token decode; the bimodal
        # generation length is intrinsic (shared by both clone copies)
        prefill, decode, gen_short, gen_long, p_long = p
        gen = jnp.where(u < p_long, gen_long, gen_short)
        return (prefill + gen * decode).astype(jnp.float32)
    raise ValueError(cfg.service.kind)


def _execute(cfg: FleetConfig, key, base):
    """One execution's runtime: per-copy randomness + the jitter spike.
    One uniform draw feeds both (inverse-CDF), keeping the tick cheap."""
    u = jax.random.uniform(key, base.shape + (2,))
    if cfg.service.kind == SERVICE_EXPONENTIAL:
        # dummy-RPC spin drawn at the server (§5.1.2)
        dur = -jnp.log1p(-u[..., 0] * (1.0 - 1e-7)) * base
    else:
        dur = base * (0.9 + 0.2 * u[..., 0])
    spike = u[..., 1] < cfg.service.jitter_p
    return jnp.where(spike, dur * cfg.service.jitter_mult, dur)


def _rank_among_earlier(mask_2d):
    """For (S, L) masks: count of earlier True lanes in the same row."""
    c = jnp.cumsum(mask_2d.astype(jnp.int32), axis=-1)
    return c - mask_2d.astype(jnp.int32)


def _rank(mask_1d):
    """Rank of each True among earlier Trues of a (L,) mask."""
    m = mask_1d.astype(jnp.int32)
    return jnp.cumsum(m) - m


# ----------------------------------------------------------------- contexts --
class Arrivals(NamedTuple):
    """Per-tick arrival context: admitted lanes + flattened fabric views."""

    tick: jax.Array        # () int32
    t_us: jax.Array        # () f32
    down: jax.Array        # () bool — fabric dark this tick
    k_exec: jax.Array      # PRNG key for the server stage's execution draws
    k_stage: jax.Array     # PRNG key for optional-stage randomness
    sstate: jax.Array      # (ST,) flat tracked queue lengths
    tables: jax.Array      # the carried filter tables, FabricSwitch layout
    active: jax.Array      # (A,) admitted arrival lanes
    grp: jax.Array         # (A,) GrpT index
    fidx: jax.Array        # (A,) filter-table index within a group
    client: jax.Array      # (A,) client id
    base: jax.Array        # (A,) intrinsic service demand (µs)
    home: jax.Array        # (A,) home rack
    pair: jax.Array        # (A, 2) GrpT pair, fabric-global ids
    r1: jax.Array          # (A,) first uniform candidate, fabric-global
    r2: jax.Array          # (A,) second uniform candidate, fabric-global
    r2_local: jax.Array    # (A,) second candidate, rack-local


class Routed(NamedTuple):
    """Route-stage outputs consumed by the optional stages."""

    req_id: jax.Array      # (A,) spine-assigned REQ_IDs
    cloned: jax.Array      # (A,) immediate-clone mask
    frack: jax.Array       # (A,) filter switch (home rack or spine)


class Lanes(NamedTuple):
    """Delivery lanes headed for the server stage.

    ``payload`` rows are ``QF``-format queue records; ``clo`` is kept as a
    separate int view (it also drives the CLO=2 drop rule).  Optional
    stages append their dispatches with :meth:`extend`.
    """

    dst: jax.Array         # (D,) int32 destination server, fabric-global
    act: jax.Array         # (D,) bool
    clo: jax.Array         # (D,) int32
    payload: jax.Array     # (D, QF) f32

    def extend(self, dst, act, clo, payload) -> "Lanes":
        return Lanes(
            dst=jnp.concatenate([self.dst, dst.astype(jnp.int32)]),
            act=jnp.concatenate([self.act, act]),
            clo=jnp.concatenate([self.clo, clo.astype(jnp.int32)]),
            payload=jnp.concatenate([self.payload, payload], axis=0),
        )


class Responses(NamedTuple):
    """Compacted completion lanes leaving the server stage."""

    active: jax.Array      # (K,) bool
    rid: jax.Array
    clo: jax.Array
    idx: jax.Array
    client: jax.Array
    tarr: jax.Array
    hop: jax.Array
    frack: jax.Array
    sid: jax.Array
    qlen: jax.Array
    # µs at which the coordinator's CPU forwards each response (set by
    # stage_coordinator_responses; None when that stage is compiled out)
    t_coord: jax.Array | None = None


# ------------------------------------------------------------------- stages --
def stage_recovery_wipe(cfg: FleetConfig, params, state: FleetState,
                        tick) -> FleetState:
    """§3.6 recovery: all switch soft state lost, REQ_IDs restart from 1;
    the clients' pending-request fingerprints of lost requests go with it.

    Runs first in the tick (:func:`with_recovery_wipe`), and only in a
    program whose runs can recover inside the horizon
    (``engine.needs_recovery_wipe``): elsewhere ``tick ==
    params.fail_until_tick`` never holds and each select is an identity."""
    recover = tick == params.fail_until_tick
    switch = jax.tree.map(
        lambda b: jnp.where(recover, jnp.zeros_like(b), b), state.switch)
    dedup = jnp.where(recover, jnp.zeros_like(state.dedup), state.dedup)
    wheel = state.wheel
    if cfg.hedge_timer:
        # pending hedge timers are switch soft state too (the DES wipes the
        # policy's outstanding map on failure)
        wheel = jax.tree.map(
            lambda b: jnp.where(recover, jnp.zeros_like(b), b), wheel)
    # the coordinator node is NOT wiped: it is a server-side CPU box, not
    # switch soft state (matching the DES, whose coordinator queue and
    # outstanding counts survive a switch failure)
    return state._replace(switch=switch, dedup=dedup, wheel=wheel)


def stage_arrival(cfg: FleetConfig, params, state: FleetState, xs):
    """Admission + attribute draws: Poisson/trace lane masking while the
    fabric is down, and the one uniform block covering every per-lane
    attribute (the ``n_racks == 1`` column layout matches the single-ToR
    engine draw for draw)."""
    RK, S, C = cfg.n_racks, cfg.n_servers, cfg.n_clients
    ST = RK * S
    T = cfg.n_filter_tables
    A = cfg.max_arrivals
    dt = jnp.float32(cfg.dt_us)

    tick, n_raw = xs
    m = state.metrics
    t_us = tick.astype(jnp.float32) * dt
    down = (tick >= params.fail_from_tick) & (tick < params.fail_until_tick)
    switch = state.switch
    # flat view of the rack-major StateT, so every per-server op is the
    # one of the single-ToR engine (the filter tables keep their layout)
    sstate = switch.server_state.reshape(ST)

    key, k_arr, k_exec = jax.random.split(state.key, 3)
    k_stage = jax.random.fold_in(k_arr, 1)

    # -- arrivals (Poisson count precomputed outside the scan) -------
    n_arr = jnp.minimum(n_raw, A)
    arr_active = jnp.arange(A) < n_arr
    m = m._replace(n_truncated=m.n_truncated + (n_raw - n_arr),
                   n_dropped_down=m.n_dropped_down
                   + jnp.where(down, n_arr, 0))
    arr_active &= ~down
    m = m._replace(n_arrivals=m.n_arrivals + arr_active.sum())

    # one uniform block covers every per-lane attribute draw (the home-
    # rack column only exists when there is more than one rack, so the
    # n_racks == 1 stream matches the single-ToR engine draw for draw)
    u = jax.random.uniform(k_arr, (A, 7 if RK > 1 else 6))

    def to_int(col, n):
        return jnp.minimum((u[:, col] * n).astype(jnp.int32), n - 1)

    grp = to_int(0, cfg.n_groups)
    fidx = to_int(1, T)
    client = to_int(2, C)
    base = _intrinsic(cfg, u[:, 3])
    r1 = to_int(4, S)
    r2 = (r1 + 1 + to_int(5, S - 1)) % S
    if RK > 1:
        # inverse-CDF pick over the (possibly skewed) rack weights
        cw = jnp.cumsum(params.rack_weights)
        home = jnp.searchsorted(cw, u[:, 6] * cw[-1],
                                side="right").astype(jnp.int32)
        home = jnp.minimum(home, RK - 1)
    else:
        home = jnp.zeros(A, jnp.int32)
    off = home * S               # local → fabric-global server ids
    state = state._replace(key=key, metrics=m)
    return state, Arrivals(
        tick=tick, t_us=t_us, down=down, k_exec=k_exec, k_stage=k_stage,
        sstate=sstate, tables=switch.filter_tables, active=arr_active,
        grp=grp, fidx=fidx, client=client, base=base, home=home,
        pair=None,               # GrpT lookup happens in stage_route
        r1=off + r1, r2=off + r2, r2_local=r2)


def stage_route(cfg: FleetConfig, params, state: FleetState, arr: Arrivals,
                group_pairs: jax.Array, xhop: jax.Array):
    """ToR routing + spine placement: every arrival lane's home rack switch
    decides locally (``route_fabric``), the spine upgrades saturated
    ``spine_clone`` lanes to inter-rack clones and assigns fabric-global
    REQ_IDs; emits the base delivery-lane group (originals then clones)."""
    RK, S = cfg.n_racks, cfg.n_servers
    A = cfg.max_arrivals
    D = 2 * A
    m = state.metrics
    switch = state.switch
    arr_active = arr.active

    pair = group_pairs[arr.grp] + (arr.home * S)[:, None]
    dst1, dst2, cloned, clo1, clo2 = route_fabric(
        params.policy_id, arr.sstate, pair, arr.r1, arr.r2, arr.home,
        arr.r2_local, n_racks=RK, n_servers=S,
        dead=link_dead(params, arr.tick))
    xrack = cloned & ((dst1 // S) != (dst2 // S))
    # the filter switch of a pair: its home rack ToR, or the spine
    # (table group RK) when the copies span racks
    frack = jnp.where(xrack, jnp.int32(RK), arr.home)
    req_id = switch.seq + 1 + jnp.arange(A, dtype=jnp.int32)
    switch = switch._replace(seq=switch.seq + jnp.int32(A))
    m = m._replace(
        n_cloned=m.n_cloned + (arr_active & cloned).sum(),
        n_interrack_cloned=m.n_interrack_cloned
        + (arr_active & xrack).sum())

    # delivery lanes: clone copies sort after originals, mirroring the
    # recirculated clone leaving the pipeline second; the remote copy of
    # an inter-rack pair carries its spine detour as a per-copy hop term
    d_dst = jnp.concatenate([dst1, dst2]).astype(jnp.int32)
    d_clo = jnp.concatenate([clo1, clo2])
    d_act = jnp.concatenate([arr_active, arr_active & cloned])
    d_hop = jnp.concatenate([jnp.zeros(A, jnp.float32),
                             jnp.where(xrack, xhop, 0.0)])
    payload = jnp.stack([                            # (D, QF)
        jnp.tile(arr.base, 2),
        jnp.full(D, arr.t_us),
        jnp.tile(req_id, 2).astype(jnp.float32),
        d_clo.astype(jnp.float32),
        jnp.tile(arr.fidx, 2).astype(jnp.float32),
        jnp.tile(arr.client, 2).astype(jnp.float32),
        d_hop,
        jnp.tile(frack, 2).astype(jnp.float32),
    ], axis=1)
    arr = arr._replace(pair=pair)
    state = state._replace(switch=switch, metrics=m)
    if cfg.telemetry:
        # REQ_IDs are assigned here at the spine, so the arrival event is
        # emitted here too (same tick; emit order preserves stage order)
        tr = emit(state.trace, arr_active, tick=arr.tick, kind=EV_ARRIVAL,
                  rid=req_id, client=arr.client, arg=arr.home)
        tr = emit(tr, arr_active, tick=arr.tick, kind=EV_ROUTE,
                  rid=req_id, server=dst1, client=arr.client,
                  arg=cloned.astype(jnp.int32))
        tr = emit(tr, arr_active & cloned, tick=arr.tick, kind=EV_CLONE,
                  rid=req_id, server=dst2, client=arr.client,
                  arg=jnp.where(xrack, CLONE_SRC_INTERRACK, CLONE_SRC_LOCAL))
        state = state._replace(trace=tr)
    lanes = Lanes(dst=d_dst, act=d_act, clo=d_clo, payload=payload)
    return state, arr, Routed(req_id=req_id, cloned=cloned, frack=frack), lanes


def _coord_send(coord, put, dst, rows):
    """Append the copies ``put`` marks (to ``dst``, ``QF`` rows whose
    ``QF_HOP`` holds the time they reach their server) to the coordinator's
    ring of copies on their way, in lane order."""
    cap = coord.out_dst.shape[0]
    at = jnp.where(put, (coord.out_head + coord.out_count + _rank(put)) % cap,
                   cap)
    return coord._replace(
        out_count=coord.out_count + put.sum(),
        out_dst=coord.out_dst.at[at].set(dst.astype(jnp.int32), mode="drop"),
        out_data=coord.out_data.at[at].set(rows, mode="drop"))


def stage_coordinator(cfg: FleetConfig, params, state: FleetState,
                      arr: Arrivals, routed: Routed, lanes: Lanes):
    """LÆDGE coordinator node, request side (compiled out unless
    ``cfg.coordinator``).  The DES's coordinator in array form:

    * arrival lanes of coordinator policies never dispatch at the switch;
      the coordinator receives them in lane order, each for one CPU pass,
      and decides at once by the policy's registered rule: two random idle
      servers when ≥ 2 are idle (the duplicate costs a second pass), the
      idle one when exactly one is, else the request waits in the ring
      (or is lost when the ring is full, ``n_coord_overflow``; its pass is
      spent all the same);
    * a copy leaves when the CPU has served its pass and reaches its
      server one link later: it waits in the ring of copies on their way
      and joins the delivery lanes in that tick, its sub-tick remainder
      kept in its hop term (≤ 0).

    The response side (``stage_coordinator_responses``) frees the
    outstanding slots and sends waiting requests.  Approximations, each
    bounded by one tick: a tick's arrivals reach the CPU together at the
    tick's start; a tick's responses reach it after those arrivals, in
    lane order, and the next tick's decisions see them."""
    if not cfg.coordinator:
        return state, lanes
    RK, W = cfg.n_racks, cfg.n_workers
    ST = RK * cfg.n_servers
    A = cfg.max_arrivals
    CQ = cfg.coordinator_cap
    P = cfg.coord_sends_per_tick
    cpu = jnp.float32(cfg.coord_cpu_us)
    link = jnp.float32(cfg.link_us)

    m = state.metrics
    coord = state.coord
    is_coord = id_mask(params.policy_id, registry.coordinator_ids())

    # coordinator lanes never dispatch directly (is_coord is a traced
    # scalar: under vmap each sweep row takes its own value)
    lanes = lanes._replace(act=lanes.act & ~is_coord)

    # -- copies reaching their server this tick (FIFO in arrival time) -----
    cap = coord.out_dst.shape[0]
    j = jnp.arange(P)
    at = (coord.out_head + j) % cap
    out = coord.out_data[at]
    due = (j < coord.out_count) & (out[:, QF_HOP] <= arr.t_us)
    pay = out.at[:, QF_HOP].set(jnp.where(due, out[:, QF_HOP] - arr.t_us,
                                          0.0))
    lanes = lanes.extend(coord.out_dst[at], due,
                         jnp.full(P, CLO_ORIG, jnp.int32), pay)
    n_due = due.sum()
    coord = coord._replace(out_head=(coord.out_head + n_due) % cap,
                           out_count=coord.out_count - n_due)

    # -- receive this tick's arrivals and decide, in lane order ------------
    got = arr.active & is_coord
    waiting = coord.count > 0
    u = jax.random.uniform(arr.k_stage, (A, 2))
    branches = registry.coordinator_branches()
    sid = jnp.arange(ST)

    def decide(outstanding, x):
        g, u_j = x
        idle = outstanding < W
        n_idle = idle.sum()
        s1, s2, want_clone = jax.lax.switch(
            params.policy_id, branches, idle, n_idle, u_j[0], u_j[1])
        # FCFS: a request never overtakes one already waiting (one waits
        # only while no server is idle, so this is the DES's order too)
        send = g & (n_idle >= 1) & ~waiting
        clone = send & want_clone
        # one-hot adds, not scatters: in the scan a 1-element scatter into
        # the (rows, ST) counts costs far more than an elementwise add
        outstanding = (outstanding + ((sid == s1) & send)
                       + ((sid == s2) & clone))
        return outstanding, (send, clone, s1, s2)

    outstanding, (send, clone, s1, s2) = jax.lax.scan(
        decide, coord.outstanding, (got, u))

    # -- the CPU serves the passes FCFS from max(now, its backlog) ---------
    clone_f = clone.astype(jnp.float32)
    cost = jnp.where(got, 1.0 + clone_f, 0.0) * cpu
    spent = jnp.cumsum(cost)
    start = jnp.maximum(arr.t_us, coord.cpu_free)
    t_dup = start + spent                 # the duplicate's own pass ends
    t_first = t_dup - clone_f * cpu       # the receipt ends: copy 1 leaves

    rows = jnp.stack([                               # (A, QF)
        arr.base,
        jnp.full(A, arr.t_us),
        routed.req_id.astype(jnp.float32),
        jnp.full(A, float(CLO_ORIG), jnp.float32),
        arr.fidx.astype(jnp.float32),
        arr.client.astype(jnp.float32),
        jnp.zeros(A, jnp.float32),
        jnp.full(A, float(RK), jnp.float32),  # pairs filter at the top tier
    ], axis=1)
    # copies are CLO_ORIG: ordinary at the servers, paired at the filter
    sent = jnp.repeat(rows, 2, axis=0).at[:, QF_HOP].set(
        (jnp.stack([t_first, t_dup], axis=1) + link).reshape(-1))
    coord = _coord_send(coord, jnp.stack([send, clone], axis=1).reshape(-1),
                        jnp.stack([s1, s2], axis=1).reshape(-1), sent)

    # -- requests with no idle server wait in the ring ---------------------
    pend = got & ~send
    rank = _rank(pend)
    ok = pend & (coord.count + rank < CQ)
    slot = (coord.head + coord.count + rank) % CQ
    data = coord.data.at[jnp.where(ok, slot, CQ)].set(rows, mode="drop")
    m = m._replace(
        n_cloned=m.n_cloned + clone.sum(),
        n_coord_queued=m.n_coord_queued + (send | ok).sum(),
        n_coord_overflow=m.n_coord_overflow + (pend & ~ok).sum(),
        n_coord_pending=m.n_coord_pending + ok.sum(),
        n_coord_packets=m.n_coord_packets + got.sum() + clone.sum())
    if cfg.telemetry:
        tr = emit(state.trace, send | ok, tick=arr.tick, kind=EV_COORD_ENQ,
                  rid=routed.req_id, client=arr.client,
                  arg=jnp.where(ok, coord.count + rank, 0))  # ring depth
        tr = emit(tr, send, tick=arr.tick, kind=EV_COORD_DISPATCH,
                  rid=routed.req_id, server=s1, client=arr.client,
                  arg=clone.astype(jnp.int32))
        tr = emit(tr, clone, tick=arr.tick, kind=EV_CLONE,
                  rid=routed.req_id, server=s2, client=arr.client,
                  arg=CLONE_SRC_COORD)
        state = state._replace(trace=tr)

    state = state._replace(
        metrics=m,
        coord=coord._replace(outstanding=outstanding, data=data,
                             count=coord.count + ok.sum(),
                             cpu_free=start + spent[-1]))
    return state, lanes


def stage_coordinator_responses(cfg: FleetConfig, params, state: FleetState,
                                arr: Arrivals, resp: Responses):
    """LÆDGE coordinator node, response side (compiled out unless
    ``cfg.coordinator``).

    Every response of a coordinator policy — the slower copy of a pair
    too, which the filter then absorbs — reaches the coordinator one link,
    a pipeline pass and a link after its server finished, frees its
    server's outstanding slot at once (the DES counts it on receipt) and
    waits for one CPU pass.  A response that frees a slot while requests
    wait sends the oldest of them there, for a pass more.  Returns the
    responses with ``t_coord``, when each leaves the CPU for its client."""
    if not cfg.coordinator:
        return state, resp
    ST = cfg.n_servers_total
    CQ = cfg.coordinator_cap
    cpu = jnp.float32(cfg.coord_cpu_us)
    link = jnp.float32(cfg.link_us)
    m = state.metrics
    coord = state.coord
    is_coord = id_mask(params.policy_id, registry.coordinator_ids())

    got = resp.active & is_coord
    tau = arr.t_us + resp.hop + (2.0 * link + cfg.pipeline_pass_us)
    rank = _rank(got)
    # one waits only while every server holds n_workers copies, so the
    # slot a response frees is the only idle one: it takes the next
    drain = got & (rank < coord.count)
    cost = jnp.where(got, 1.0 + drain.astype(jnp.float32), 0.0) * cpu
    after = jnp.cumsum(cost)
    before = after - cost
    # FCFS from max(arrival, backlog): the CPU is free after lane k at
    # after_k + max(cpu_free, max_{j<=k} tau_j - before_j)
    late = jax.lax.cummax(jnp.where(got, tau - before, -jnp.inf))
    base = jnp.maximum(coord.cpu_free, late)
    t_resp = base + before + cpu          # the response leaves the CPU
    t_send = base + after                 # … and the request it frees
    prev_base = jnp.maximum(
        coord.cpu_free, jnp.concatenate([jnp.full(1, -jnp.inf), late[:-1]]))
    waited = got & (prev_base + before > tau)

    outstanding = coord.outstanding.at[
        jnp.where(got & ~drain, resp.sid, ST)].add(-1, mode="drop")
    rows = coord.data[(coord.head + rank) % CQ]
    n_drain = drain.sum()
    coord = _coord_send(coord._replace(
        outstanding=outstanding, head=(coord.head + n_drain) % CQ,
        count=coord.count - n_drain,
        cpu_free=base[-1] + after[-1]), drain, resp.sid,
        rows.at[:, QF_HOP].set(t_send + link))
    m = m._replace(
        n_coord_packets=m.n_coord_packets + got.sum() + n_drain,
        n_coord_resp_waited=m.n_coord_resp_waited + waited.sum())
    state = state._replace(metrics=m, coord=coord)
    if cfg.telemetry:
        tr = emit(state.trace, got, tick=arr.tick, kind=EV_COORD_RESP,
                  rid=resp.rid, server=resp.sid, client=resp.client,
                  arg=jnp.round(t_resp - cpu - tau))  # arg: wait (µs)
        tr = emit(tr, drain, tick=arr.tick, kind=EV_COORD_DISPATCH,
                  rid=rows[:, QF_RID].astype(jnp.int32), server=resp.sid,
                  client=rows[:, QF_CLIENT].astype(jnp.int32), arg=0)
        state = state._replace(trace=tr)
    return state, resp._replace(t_coord=t_resp)


def wheel_arm(wheel: HedgeWheel, tick, delay_ticks, arm_mask,
              entries):
    """Arm ``entries`` (rows of ``WH`` fields, one per True in
    ``arm_mask``) to fire ``delay_ticks`` from ``tick`` (``delay_ticks``
    may be a traced scalar — the delay is a sweep axis).

    Returns ``(wheel, armed_mask, dropped_mask)``: lanes beyond the slot's
    free width are dropped *deterministically* — the latest lanes lose, and
    a lane is never dropped while the slot has room (property-tested in
    ``tests/test_fleetsim_stages.py``)."""
    n_slots, width, _ = wheel.data.shape
    slot = (tick + delay_ticks) % n_slots
    pos = wheel.count[slot] + _rank(arm_mask)
    ok = arm_mask & (pos < width)
    data = wheel.data.at[slot, jnp.where(ok, pos, width)].set(
        entries, mode="drop")
    count = wheel.count.at[slot].add(ok.sum())
    return wheel._replace(count=count, data=data), ok, arm_mask & ~ok


def wheel_fire(wheel: HedgeWheel, tick):
    """Pop every entry due at ``tick`` (the wheel is deeper than the delay
    horizon, so everything in the slot is due).  Returns ``(wheel,
    due_mask, entries)`` with the slot cleared (its cancel marks too)."""
    n_slots, width, _ = wheel.data.shape
    slot = tick % n_slots
    due = jnp.arange(width) < wheel.count[slot]
    entries = wheel.data[slot]
    return wheel._replace(count=wheel.count.at[slot].set(0),
                          cancelled=wheel.cancelled.at[slot].set(False)), \
        due, entries


def stage_hedge_timer(cfg: FleetConfig, params, state: FleetState,
                      arr: Arrivals, routed: Routed, lanes: Lanes):
    """Delayed hedging (compiled out unless ``cfg.hedge_timer``).

    Fires this tick's due duplicates as CLO=2 delivery lanes — unless the
    original's response already passed its switch, which
    ``stage_hedge_cancel`` marked on the entry (the DES's
    cancel-on-first-response) — then arms a wheel entry for every
    hedge-policy arrival."""
    if not cfg.hedge_timer:
        return state, lanes
    A = cfg.max_arrivals
    m = state.metrics
    is_hedge = id_mask(params.policy_id, registry.hedge_timer_ids())

    # -- fire due entries --------------------------------------------------
    gone = state.wheel.cancelled[arr.tick % state.wheel.count.shape[0]]
    wheel, due, entries = wheel_fire(state.wheel, arr.tick)
    rid = entries[:, WHEEL_RID].astype(jnp.int32)
    fire = due & ~gone & ~arr.down       # a dark fabric loses the hedge
    cancelled = due & ~fire
    HW = fire.shape[0]
    pay = jnp.stack([                                # (HW, QF)
        entries[:, WHEEL_BASE],
        entries[:, WHEEL_TARR],         # latency runs from the ORIGINAL
        entries[:, WHEEL_RID],          # arrival, so the hedge pays the
        jnp.full(HW, float(CLO_CLONE), jnp.float32),  # delay floor
        entries[:, WHEEL_IDX],
        entries[:, WHEEL_CLIENT],
        jnp.zeros(HW, jnp.float32),
        entries[:, WHEEL_FRACK],
    ], axis=1)
    lanes = lanes.extend(entries[:, WHEEL_DST].astype(jnp.int32), fire,
                         jnp.full(HW, CLO_CLONE, jnp.int32), pay)
    m = m._replace(n_cloned=m.n_cloned + fire.sum(),
                   n_hedges_cancelled=m.n_hedges_cancelled
                   + cancelled.sum())
    if cfg.telemetry:
        cli_w = entries[:, WHEEL_CLIENT].astype(jnp.int32)
        dst_w = entries[:, WHEEL_DST].astype(jnp.int32)
        tr = emit(state.trace, fire, tick=arr.tick, kind=EV_CLONE,
                  rid=rid, server=dst_w, client=cli_w, arg=CLONE_SRC_HEDGE)
        tr = emit(tr, cancelled, tick=arr.tick, kind=EV_HEDGE_CANCELLED,
                  rid=rid, server=dst_w, client=cli_w)
        state = state._replace(trace=tr)

    # -- arm this tick's arrivals ------------------------------------------
    dst2 = jax.lax.switch(params.policy_id, registry.hedge_timer_branches(),
                          arr.pair, arr.r1, arr.r2)
    rows = jnp.stack([                               # (A, WH)
        routed.req_id.astype(jnp.float32),
        dst2.astype(jnp.float32),
        arr.fidx.astype(jnp.float32),
        arr.client.astype(jnp.float32),
        arr.base,
        jnp.full(A, arr.t_us),
        routed.frack.astype(jnp.float32),
    ], axis=1)
    assert rows.shape[1] == WH
    # the delay is a *traced* per-run value (RunParams.hedge_delay_ticks),
    # so one vmapped/sharded program maps the whole delay/load plane; the
    # static wheel depth bounds it (checked by engine.check_hedge_delay)
    wheel, armed, dropped = wheel_arm(wheel, arr.tick,
                                      params.hedge_delay_ticks,
                                      arr.active & is_hedge, rows)
    m = m._replace(n_hedges_armed=m.n_hedges_armed + armed.sum(),
                   n_wheel_dropped=m.n_wheel_dropped + dropped.sum())
    state = state._replace(metrics=m, wheel=wheel)
    if cfg.telemetry:
        state = state._replace(trace=emit(
            state.trace, armed, tick=arr.tick, kind=EV_HEDGE_ARMED,
            rid=routed.req_id, server=dst2, client=arr.client,
            arg=params.hedge_delay_ticks))  # arg: delay (ticks)
    return state, lanes


def stage_hedge_cancel(cfg: FleetConfig, params, state: FleetState,
                       arr: Arrivals, resp: Responses) -> FleetState:
    """Cancel the pending hedge of every request whose response passes its
    switch this tick (compiled out unless ``cfg.hedge_timer``): the DES
    drops the request's timer on its first response, before filtering.

    The entry sits in the slot its arrival tick armed, found by REQ_ID, so
    the cancel is exact.  At tick granularity a response cancels the
    hedges due from the next tick on (the DES: those due 1.4 µs — a link,
    a pipeline pass and a link — after it reached its switch)."""
    if not cfg.hedge_timer:
        return state
    wheel = state.wheel
    n_slots, width, _ = wheel.data.shape
    is_hedge = id_mask(params.policy_id, registry.hedge_timer_ids())
    armed_at = jnp.round(resp.tarr / cfg.dt_us).astype(jnp.int32)
    slot = (armed_at + params.hedge_delay_ticks) % n_slots   # (K,)
    col = jnp.arange(width)
    hit = ((resp.active & is_hedge)[:, None]
           & (col[None, :] < wheel.count[slot][:, None])
           & (wheel.data[slot, :, WHEEL_RID]
              == resp.rid.astype(jnp.float32)[:, None]))    # (K, width)
    cancelled = wheel.cancelled.at[jnp.where(hit, slot[:, None], n_slots),
                                   col[None, :]].set(True, mode="drop")
    return state._replace(wheel=wheel._replace(cancelled=cancelled))


def stage_server(cfg: FleetConfig, params, state: FleetState,
                 arr: Arrivals, lanes: Lanes):
    """Workers advance, server-side CLO=2 drop rule, FCFS ring enqueue, and
    dequeue of the oldest queued jobs onto the freed workers (execution
    times drawn here: intrinsic base × per-execution noise × straggler
    slowdown + jitter spikes).

    ``cfg.server_model`` is a static flag: ``"batch"`` dispatches to the
    continuous-batching slot stage (ServeSim,
    :func:`repro.fleetsim.llmserve.stage.stage_server_batch`) and the FCFS
    body below is never traced; ``"fcfs"`` (default) traces exactly the
    program it always did, so the goldens stay bit-identical."""
    if cfg.server_model == "batch":
        # deferred import: llmserve.stage reuses this module's helpers
        from repro.fleetsim.llmserve.stage import stage_server_batch

        return stage_server_batch(cfg, params, state, arr, lanes)
    RK, S, W, Q = cfg.n_racks, cfg.n_servers, cfg.n_workers, cfg.queue_cap
    ST = RK * S
    D = lanes.dst.shape[0]
    dt = jnp.float32(cfg.dt_us)
    srv_ids = jnp.arange(ST)
    m = state.metrics
    d_dst, d_act, d_clo = lanes.dst, lanes.act, lanes.clo

    # -- workers advance, completions (busy ⇔ REM > 0) ---------------
    meta = state.workers.meta.reshape(ST, W, WF)
    was_busy = meta[:, :, WF_REM] > 0
    rem = jnp.where(was_busy, meta[:, :, WF_REM] - dt, 0.0)
    done = was_busy & (rem <= 0)                     # (ST, W)
    busy_after = was_busy & ~done
    n_free = (~busy_after).sum(axis=1)               # (ST,)
    rq = state.queues
    q_head = rq.head.reshape(ST)
    n_queued = rq.count.reshape(ST)

    # -- CLO=2 drop rule --------------------------------------------
    # A clone is dropped iff the server's *wait queue* is non-empty when
    # it arrives.  This tick's completions drain min(n_free, n_queued)
    # jobs first; earlier arrival lanes to the same server then occupy
    # the leftover free workers before queuing.  Two passes resolve the
    # (rare) dependence of one clone's fate on an earlier clone's.
    q_left = jnp.maximum(n_queued - n_free, 0)       # still waiting
    free_left = jnp.maximum(n_free - n_queued, 0)    # still free
    onehot = (d_dst[None, :] == srv_ids[:, None])    # (ST, D)
    is_clone = d_clo == CLO_CLONE
    n_earlier = _rank_among_earlier(onehot & (d_act & ~is_clone)[None, :])
    occupied = (q_left[d_dst] > 0) | \
        (jnp.take_along_axis(n_earlier, d_dst[None, :], axis=0)[0]
         > free_left[d_dst])
    drop0 = is_clone & d_act & occupied
    keep0 = d_act & ~drop0
    n_earlier1 = _rank_among_earlier(onehot & keep0[None, :])
    occupied1 = (q_left[d_dst] > 0) | \
        (jnp.take_along_axis(n_earlier1, d_dst[None, :], axis=0)[0]
         > free_left[d_dst])
    clone_drop = is_clone & d_act & occupied1
    d_keep = d_act & ~clone_drop
    m = m._replace(n_clone_drops=m.n_clone_drops + clone_drop.sum())

    # -- enqueue into the FCFS rings ---------------------------------
    # the r-th kept lane for a server lands r slots past its tail
    lane_m = onehot & d_keep[None, :]                # (ST, D)
    lane_rank = _rank_among_earlier(lane_m)          # (ST, D)
    rank_own = jnp.take_along_axis(lane_rank, d_dst[None, :], axis=0)[0]
    ovf = d_keep & (n_queued[d_dst] + rank_own >= Q)
    m = m._replace(n_overflow=m.n_overflow + ovf.sum())
    enq_ok = d_keep & ~ovf
    slot = (q_head[d_dst] + n_queued[d_dst] + rank_own) % Q
    flat_q = rq.data.reshape(ST * Q, QF)
    qrow = jnp.where(enq_ok, d_dst * Q + slot, jnp.int32(ST * Q))
    flat_q = flat_q.at[qrow].set(lanes.payload, mode="drop")
    count1 = n_queued + (onehot & enq_ok[None, :]).sum(axis=1)

    # -- dequeue: ring head onto free workers ------------------------
    R = min(W, Q)
    n_start = jnp.minimum(count1, n_free)            # (ST,)
    r = jnp.arange(R)
    startm = r[None, :] < n_start[:, None]           # (ST, R)
    deq_slot = (q_head[:, None] + r[None, :]) % Q    # (ST, R)
    job = flat_q[srv_ids[:, None] * Q + deq_slot]    # (ST, R, QF)
    # r-th free worker of each server, via rank matching (no sort)
    wfree = ~busy_after
    wrank = _rank_among_earlier(wfree)               # (ST, W)
    sel = (wfree[:, None, :]
           & (wrank[:, None, :] == r[None, :, None]))  # (ST, R, W)
    wcol = jnp.einsum("srw,w->sr", sel.astype(jnp.int32), jnp.arange(W))
    start_base = job[:, :, QF_BASE]
    exec_dur = _execute(cfg, arr.k_exec, start_base) \
        * params.slowdown[:, None]
    wrow = jnp.where(startm, srv_ids[:, None] * W + wcol,
                     jnp.int32(ST * W))
    # responses are read from the PRE-overwrite worker metadata
    meta_flat = jnp.concatenate(
        [jnp.where(busy_after, rem, 0.0)[:, :, None],
         meta[:, :, 1:]], axis=2).reshape(ST * W, WF)
    new_meta = jnp.stack([
        exec_dur + cfg.server_overhead_us,
        job[:, :, QF_TARR], job[:, :, QF_RID], job[:, :, QF_CLO],
        job[:, :, QF_IDX], job[:, :, QF_CLIENT],
        job[:, :, QF_HOP], job[:, :, QF_FRACK]], axis=2)   # (ST, R, WF)
    worker_meta = meta_flat.at[wrow.reshape(-1)].set(
        new_meta.reshape(-1, WF), mode="drop").reshape(ST, W, WF)
    q_count = count1 - n_start
    queues = rq._replace(head=((q_head + n_start) % Q).reshape(RK, S),
                         count=q_count.reshape(RK, S),
                         data=flat_q.reshape(RK, S, Q, QF))

    # -- compact completions into the response lanes -----------------
    K = min(cfg.max_responses, ST * W)
    done_flat = done.reshape(-1)                     # (ST·W,)
    m = m._replace(
        n_resp=m.n_resp + done_flat.sum(),
        n_resp_empty=m.n_resp_empty
        + (done_flat & (jnp.repeat(q_count, W) == 0)).sum(),
        lost_down_resp=m.lost_down_resp
        + jnp.where(arr.down, done_flat.sum(), 0))
    rrank = jnp.cumsum(done_flat) - done_flat.astype(jnp.int32)
    clipped = done_flat & (rrank >= K)
    m = m._replace(n_resp_clipped=m.n_resp_clipped + clipped.sum())
    krow = jnp.where(done_flat & ~clipped, rrank, jnp.int32(K))
    resp_payload = jnp.concatenate([                 # (ST·W, WF + 2)
        meta_flat,
        jnp.repeat(srv_ids, W).astype(jnp.float32)[:, None],
        jnp.repeat(q_count, W).astype(jnp.float32)[:, None]], axis=1)
    resp = jnp.zeros((K, WF + 2), jnp.float32).at[krow].set(
        resp_payload, mode="drop")
    n_done = jnp.minimum(done_flat.sum(), K)
    resp_active = (jnp.arange(K) < n_done) & ~arr.down

    state = state._replace(
        queues=queues,
        workers=state.workers._replace(meta=worker_meta.reshape(RK, S, W,
                                                                WF)),
        metrics=m)
    if cfg.telemetry:
        # finishes before starts: completions free the workers the dequeued
        # jobs then occupy, and emit order is the within-tick order
        tr = emit(state.trace, done_flat, tick=arr.tick,
                  kind=EV_SERVER_FINISH,
                  rid=meta_flat[:, WF_RID].astype(jnp.int32),
                  server=jnp.repeat(srv_ids, W),
                  client=meta_flat[:, WF_CLIENT].astype(jnp.int32),
                  arg=jnp.repeat(q_count, W))  # arg: post-dequeue qlen
        tr = emit(tr, startm.reshape(-1), tick=arr.tick,
                  kind=EV_SERVER_START,
                  rid=job[:, :, QF_RID].reshape(-1).astype(jnp.int32),
                  server=jnp.repeat(srv_ids, R),
                  client=job[:, :, QF_CLIENT].reshape(-1).astype(jnp.int32),
                  arg=job[:, :, QF_CLO].reshape(-1).astype(jnp.int32))
        state = state._replace(trace=tr)
    return state, Responses(
        active=resp_active,
        rid=resp[:, WF_RID].astype(jnp.int32),
        clo=resp[:, WF_CLO].astype(jnp.int32),
        idx=resp[:, WF_IDX].astype(jnp.int32),
        client=resp[:, WF_CLIENT].astype(jnp.int32),
        tarr=resp[:, WF_TARR],
        hop=resp[:, WF_HOP],
        frack=resp[:, WF_FRACK].astype(jnp.int32),
        sid=resp[:, WF].astype(jnp.int32),
        qlen=resp[:, WF + 1].astype(jnp.int32))


def stage_response_filter(cfg: FleetConfig, params, state: FleetState,
                          arr: Arrivals, resp: Responses):
    """Switch response path: per-rack StateT update + the fingerprint
    filter at each pair's filter switch (one call for the whole fabric)."""
    RK, S = cfg.n_racks, cfg.n_servers
    T = cfg.n_filter_tables
    m = state.metrics
    # each response updates its own rack switch's StateT and runs the
    # fingerprint filter at the pair's filter switch; numbering the
    # (rack | spine) × table rows lets one call serve the whole fabric
    idx_flat = resp.frack * T + resp.idx
    sstate, tables, drop = _filter_responses(
        cfg, arr.sstate, arr.tables, resp.rid, idx_flat, resp.clo, resp.sid,
        resp.qlen, resp.active)
    switch = state.switch._replace(server_state=sstate.reshape(RK, S),
                                   filter_tables=tables)
    m = m._replace(
        n_filtered=m.n_filtered + (drop & resp.active).sum(),
        n_spine_filtered=m.n_spine_filtered
        + (drop & resp.active & (resp.frack == RK)).sum())
    state = state._replace(switch=switch, metrics=m)
    if cfg.telemetry:
        state = state._replace(trace=emit(
            state.trace, drop & resp.active, tick=arr.tick,
            kind=EV_FILTER_DROP, rid=resp.rid, server=resp.sid,
            client=resp.client, arg=resp.frack))  # arg: filter switch

    return state, drop


def stage_client(cfg: FleetConfig, params, state: FleetState,
                 arr: Arrivals, resp: Responses, drop, const_lat):
    """Client receiver threads: dedup of redundant copies, FCFS backlog
    with per-response RX cost, latency recording into the per-rack
    log-spaced histograms."""
    RK, S, C = cfg.n_racks, cfg.n_servers, cfg.n_clients
    dt = jnp.float32(cfg.dt_us)
    t0_us = jnp.float32(cfg.warmup_us)
    t1_us = jnp.float32(cfg.duration_us)
    log_g = float(np.log(cfg.hist_growth))
    m = state.metrics

    deliver = resp.active & ~drop
    dedup, redundant, evicted = dedup_tick(state.dedup, resp.rid, deliver)
    first = deliver & ~redundant
    m = m._replace(n_redundant=m.n_redundant + redundant.sum(),
                   n_dedup_evicted=m.n_dedup_evicted + evicted,
                   n_completed=m.n_completed + first.sum())
    # receiver threads: FCFS backlog with per-response RX cost
    cli_onehot = (resp.client[None, :] == jnp.arange(C)[:, None]) \
        & deliver[None, :]                           # (C, K)
    pos = jnp.take_along_axis(_rank_among_earlier(cli_onehot),
                              resp.client[None, :], axis=0)[0]
    backlog_pre = jnp.maximum(state.client_backlog - dt, 0.0)
    wait = backlog_pre[resp.client] + (pos + 1) * cfg.client_rx_us
    backlog = backlog_pre + cli_onehot.sum(axis=1) * cfg.client_rx_us
    t_fin = arr.t_us + wait
    lat = t_fin - resp.tarr + const_lat + resp.hop
    if cfg.coordinator:
        # a coordinator's response leaves its CPU at t_coord and reaches
        # the client a link later; the CPU spaces its responses by
        # coord_cpu_us ≥ client_rx_us, so none waits for the receiver.
        # The request reached the coordinator at tarr, a client TX, two
        # links and a pipeline pass after it was sent
        is_coord = id_mask(params.policy_id, registry.coordinator_ids())
        t_coord = resp.t_coord + cfg.link_us + cfg.client_rx_us
        t_fin = jnp.where(is_coord, t_coord, t_fin)
        lat = jnp.where(is_coord, t_coord - resp.tarr
                        + (cfg.client_tx_us + 2 * cfg.link_us
                           + cfg.pipeline_pass_us + cfg.spine_extra_us),
                        lat)
    rec = first & (t_fin >= t0_us) & (t_fin <= t1_us)
    bins = jnp.clip((jnp.log(jnp.maximum(lat, cfg.hist_lo_us)
                             / cfg.hist_lo_us) / log_g),
                    0, cfg.hist_bins - 1).astype(jnp.int32)
    bins = jnp.where(rec, bins, cfg.hist_bins)
    # per-rack histograms, binned by the rack that served the winning
    # response (non-recorded lanes scatter out of bounds and drop)
    m = m._replace(hist=m.hist.at[resp.sid // S, bins].add(1, mode="drop"),
                   n_completed_win=m.n_completed_win + rec.sum())
    state = state._replace(dedup=dedup, client_backlog=backlog, metrics=m)
    if cfg.telemetry:
        tr = emit(state.trace, first, tick=arr.tick,
                  kind=EV_CLIENT_COMPLETE, rid=resp.rid, server=resp.sid,
                  client=resp.client,
                  arg=jnp.round(lat).astype(jnp.int32))  # arg: latency (µs)
        tr = emit(tr, redundant, tick=arr.tick, kind=EV_CLIENT_REDUNDANT,
                  rid=resp.rid, server=resp.sid, client=resp.client)
        series = series_record_hist(state.series,
                                    arr.tick // cfg.window_ticks, bins)
        state = state._replace(trace=tr, series=series)
    return state


def _filter_responses(cfg, server_state, tables, rid, idx, clo, sid, qlen,
                      active):
    """Response path over the flattened fabric: StateT/ShadowT update + the
    fingerprint filter, with the backend chosen at compile time.

    ``server_state`` is the flat ``(n_racks·S,)`` tracked view, ``tables``
    the carried tables of every rack's filter group plus the spine's
    (``FabricSwitch.filter_tables``), and ``idx`` the table pre-offset
    into their ``(n_racks+1)·n_tables`` — so a lane's (req_id, idx) group is
    unique per filter switch and the one-call semantics match per-switch
    sequential filtering exactly.
    """
    # inactive lanes never touch StateT: an out-of-range sid is dropped
    sid_m = jnp.where(active, sid.astype(jnp.int32),
                      jnp.int32(server_state.shape[0]))
    if cfg.filter_backend == "vectorized":
        server_state = server_state.at[sid_m].set(
            qlen.astype(jnp.int32), mode="drop")
        tables, drop = filter_rows(tables, cfg.n_filter_slots, rid, idx,
                                   clo, active)
        return server_state, tables, drop
    # scan / pallas / tickfuse take the (table, slot) stack: CLO=0 lanes
    # never touch the filter
    clo_m = jnp.where(active, clo, 0).astype(jnp.int32)
    stack = tables.reshape(-1, cfg.n_filter_slots)
    if cfg.filter_backend == "tickfuse":
        # the fused megakernel: StateT write + fingerprint filter in one
        # launch, both tables resident (TickFuse, kernels/tickfuse.py)
        from repro.kernels.ops import tickfuse_response_path

        server_state, stack, drop = tickfuse_response_path(
            server_state, stack, rid.astype(jnp.int32),
            idx.astype(jnp.int32), clo_m, sid_m, qlen.astype(jnp.int32))
        return server_state, stack.reshape(tables.shape), drop
    # scan / pallas: StateT via a masked scatter, then the table update
    server_state = server_state.at[sid_m].set(
        qlen.astype(jnp.int32), mode="drop")
    if cfg.filter_backend == "scan":
        stack, drop = jax.lax.scan(
            _filter_step, stack,
            (rid.astype(jnp.int32), idx.astype(jnp.int32), clo_m))
    else:  # pallas — the VMEM-resident fingerprint kernel
        from repro.kernels.ops import fingerprint_filter

        stack, drop = fingerprint_filter(
            stack, rid.astype(jnp.int32), idx.astype(jnp.int32), clo_m)
    return server_state, stack.reshape(tables.shape), drop


# ---------------------------------------------------------------- pipeline --
def build_step(cfg: FleetConfig, params, group_pairs: jax.Array):
    """Compose the stages into the tick function ``lax.scan`` advances.

    The composition is the whole engine: a policy that needs different
    behaviour plugs into a stage through the registry (route branch, spine
    placement, coordinator rule, hedge destination) instead of forking
    this function.

    Each stage runs under a ``jax.named_scope`` (``tick.<stage>``), so
    every op the tick compiles to carries its stage in its ``op_name``
    metadata and a profiler trace attributes device time per stage.  The
    scopes are metadata only: the compiled ops and results do not change.
    """
    # in-network constants added to every recorded latency (client TX + four
    # link hops + two pipeline passes + the spine tier's round trip when the
    # fabric has one; client-duplicating policies — C-Clone and any custom
    # registration flagged client_dup — pay the doubled sender cost)
    const_lat = (cfg.client_tx_us + 4 * cfg.link_us + 2 * cfg.pipeline_pass_us
                 + cfg.spine_extra_us
                 + jnp.where(id_mask(params.policy_id,
                                     registry.client_dup_ids()),
                             cfg.client_tx_us, 0.0))
    xhop = jnp.float32(cfg.interrack_extra_us)

    def step(state: FleetState, xs):
        with jax.named_scope("tick.arrival"):
            state, arr = stage_arrival(cfg, params, state, xs)
        with jax.named_scope("tick.route"):
            state, arr, routed, lanes = stage_route(cfg, params, state, arr,
                                                    group_pairs, xhop)
        with jax.named_scope("tick.coordinator"):
            state, lanes = stage_coordinator(cfg, params, state, arr,
                                             routed, lanes)
        with jax.named_scope("tick.hedge_timer"):
            state, lanes = stage_hedge_timer(cfg, params, state, arr,
                                             routed, lanes)
        # ChaosFuzz link failures (repro.fleetsim.chaos): copies onto a
        # dead link vanish before the servers, responses from partitioned
        # servers vanish before the filter switch.  Inert windows keep
        # both stages value-identical to the pre-chaos pipeline.
        with jax.named_scope("tick.link"):
            state, lanes = stage_link_failure(cfg, params, state, arr, lanes)
        with jax.named_scope("tick.server"):
            state, resp = stage_server(cfg, params, state, arr, lanes)
        with jax.named_scope("tick.link"):
            state, resp = stage_link_response(cfg, params, state, arr, resp)
        with jax.named_scope("tick.filter"):
            state, drop = stage_response_filter(cfg, params, state, arr,
                                                resp)
        with jax.named_scope("tick.coordinator"):
            state, resp = stage_coordinator_responses(cfg, params, state,
                                                      arr, resp)
        with jax.named_scope("tick.hedge_timer"):
            state = stage_hedge_cancel(cfg, params, state, arr, resp)
        with jax.named_scope("tick.client"):
            state = stage_client(cfg, params, state, arr, resp, drop,
                                 const_lat)
        if cfg.telemetry:
            with jax.named_scope("tick.telemetry"):
                state = state._replace(series=series_tick(
                    cfg, state.series, state.metrics, state.queues.count,
                    arr.tick))
        return state, None

    return step


def with_recovery_wipe(cfg: FleetConfig, params, step):
    """``step`` opened by :func:`stage_recovery_wipe`, under
    ``tick.arrival/tick.wipe``: the tick of a program in which some run's
    switch recovers inside the horizon.  The engines wrap
    :func:`build_step` in it only then; a window-free program carries no
    select over the switch tables."""
    def wiped(state: FleetState, xs):
        with jax.named_scope("tick.arrival"), jax.named_scope("tick.wipe"):
            state = stage_recovery_wipe(cfg, params, state, xs[0])
        return step(state, xs)

    return wiped
