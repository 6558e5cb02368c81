"""FleetSim engine: one ``lax.scan`` advances the fabric, ``vmap`` sweeps it
(and ``repro.fleetsim.shard`` spreads the sweep grid over a device mesh).

Fixed-timestep (``dt_us``) time-stepped simulation of the full NetClone
testbed — open-loop Poisson clients, a 2-tier switch fabric (per-rack ToR
switches with GrpT/StateT/FilterT under a spine that assigns fabric-global
REQ_IDs, aggregates per-rack load, and filters inter-rack clone pairs),
FCFS multi-worker servers with the CLO=2 stale-state drop rule, and client
receiver threads with per-response RX cost and redundant-response dedup.
The entire cluster lives in :class:`FleetState` arrays.

A tick is the **staged pipeline** composed in
:func:`repro.fleetsim.stages.build_step`:

    arrival → route (ToR + spine) → coordinator → hedge_timer
            → server → response/filter → client

Each stage is a pure function over the fleet state; the coordinator
(LÆDGE's CPU queue node) and hedge_timer (the delayed-duplicate timer
wheel) stages are compiled in only when the static ``FleetConfig`` flags
ask for them, so the flag-off program is exactly the pre-stage engine —
see ``stages.py`` for the per-stage semantics and the registry hooks
policies use to plug in.

Feedback staleness is one tick: responses processed at tick *t* steer
routing from tick *t+1*, matching the ≈1 µs server→switch path of the DES.

With ``n_racks == 1`` the fabric reduces *bit-identically* to the original
single-ToR engine (same PRNG draws in the same order, same op order; the
spine tier contributes zero latency and its filter group is never
addressed) — enforced by the golden test in ``tests/test_fleetsim_fabric``.

Deliberate approximations vs the DES (documented for the cross-validation
tolerances in ``validate.py``): latencies quantize to ``dt``; in-network
constants are folded into a per-request additive term instead of delaying
state feedback; the clone recirculation pass (0.4 µs < dt) is not modelled;
queue capacity and per-tick response lanes are finite (both overflows are
counted and sized to be vanishingly rare below saturation).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.switch_jax import group_pairs_array
from repro.fleetsim.config import FleetConfig
from repro.fleetsim.stages import build_step, with_recovery_wipe
from repro.fleetsim.state import FleetState, init_fleet_state
from repro.scenarios import registry


class RunParams(NamedTuple):
    """Per-run traced inputs — the axes a sweep maps over."""

    policy_id: jax.Array      # () int32
    rate_per_us: jax.Array    # () f32 — offered arrival rate
    seed: jax.Array           # () int32
    slowdown: jax.Array       # (n_racks · S,) f32 — straggler multipliers
    rack_weights: jax.Array   # (n_racks,) f32 — arrival-skew weights
    fail_from_tick: jax.Array  # () int32 — fabric dark from this tick …
    fail_until_tick: jax.Array  # () int32 — … until this tick (then wiped)
    # per-tick arrival counts for cfg.arrival == "trace" (shape (n_ticks,));
    # (0,) for Poisson runs, whose counts the device draws itself
    arrival_counts: jax.Array
    # () int32 — hedge-timer delay in ticks.  A *traced* sweep axis (one
    # program maps the delay/load plane, see sweep_grid's hedge_delays);
    # defaults to the static cfg.hedge_delay_ticks and is ignored — but
    # still carried — when the hedge_timer stage is compiled out.  (The
    # default is a plain int so importing this module does not create a
    # device array; every construction path fills it explicitly.)
    hedge_delay_ticks: jax.Array | int = 0
    # ChaosFuzz link-failure window (repro.fleetsim.chaos): dead links from
    # link_from_tick until link_until_tick over the (n_racks·S,) bool
    # link_mask.  Traced per-run inputs like fail_*_tick, so heterogeneous
    # failure campaigns ride in one vmapped sweep; the inert default —
    # window past the horizon, all-false mask — keeps results bit-identical.
    # (Plain ints for the same import-time reason as hedge_delay_ticks.)
    link_from_tick: jax.Array | int = 0
    link_until_tick: jax.Array | int = 0
    link_mask: jax.Array | int = 0


def check_fabric_arrays(cfg: FleetConfig, slowdown=None, rack_weights=None,
                        ) -> tuple[np.ndarray, np.ndarray]:
    """Default + shape-check the per-fabric run inputs (shared by
    :func:`make_params` and ``sweep.sweep_grid``): ``slowdown`` flattens
    ``(n_racks, n_servers)`` to ``(n_racks·n_servers,)``, ``rack_weights``
    must carry one weight per rack."""
    if slowdown is None:
        slowdown = np.ones(cfg.n_servers_total, np.float32)
    slowdown = np.asarray(slowdown, np.float32).reshape(-1)
    if slowdown.shape != (cfg.n_servers_total,):
        raise ValueError(f"slowdown must have n_racks*n_servers="
                         f"{cfg.n_servers_total} entries, got "
                         f"{slowdown.shape}")
    if rack_weights is None:
        rack_weights = np.ones(cfg.n_racks, np.float32)
    rack_weights = np.asarray(rack_weights, np.float32)
    if rack_weights.shape != (cfg.n_racks,):
        raise ValueError(f"rack_weights must have n_racks={cfg.n_racks} "
                         f"entries, got {rack_weights.shape}")
    return slowdown, rack_weights


def check_arrival_counts(cfg: FleetConfig, arrival_counts) -> np.ndarray:
    """Default + shape-check the per-tick trace counts: ``(n_ticks,)`` for
    trace runs, empty for Poisson (whose counts the device draws)."""
    if cfg.arrival == "trace":
        if arrival_counts is None:
            raise ValueError('cfg.arrival == "trace" needs arrival_counts '
                             "(see repro.scenarios.arrival.TraceArrival)")
        arrival_counts = np.asarray(arrival_counts, np.int32).reshape(-1)
        if arrival_counts.shape != (cfg.n_ticks,):
            raise ValueError(f"arrival_counts must have n_ticks="
                             f"{cfg.n_ticks} entries, got "
                             f"{arrival_counts.shape}")
        return arrival_counts
    if arrival_counts is not None:
        raise ValueError("arrival_counts passed but cfg.arrival is "
                         f"{cfg.arrival!r}")
    return np.zeros((0,), np.int32)


def check_policy_stages(cfg: FleetConfig, policy_id: int) -> None:
    """A policy that needs an optional stage cannot run on a config that
    compiled it out — fail at params construction, not with silent
    zero-traffic results."""
    name = registry.policy_name_map().get(int(policy_id))
    if name is None:
        return
    if registry.needs_coordinator(name) and not cfg.coordinator:
        raise ValueError(
            f"policy {name!r} needs the coordinator stage; build the "
            "config with coordinator=True (Scenario / sweep_grid do this "
            "automatically via FleetConfig.with_policy_stages)")
    if registry.needs_hedge_timer(name) and not cfg.hedge_timer:
        raise ValueError(
            f"policy {name!r} needs the hedge_timer stage; build the "
            "config with hedge_timer=True (Scenario / sweep_grid do this "
            "automatically via FleetConfig.with_policy_stages)")


def check_hedge_delay(cfg: FleetConfig,
                      hedge_delay_us: float | None) -> int:
    """Resolve a per-run hedge delay to ticks and bound it by the static
    wheel depth (shared by :func:`make_params` and ``sweep.sweep_grid``).
    ``None`` means the config's own ``hedge_delay_us``."""
    if hedge_delay_us is None:
        return cfg.hedge_delay_ticks
    if hedge_delay_us <= 0:
        raise ValueError("hedge_delay_us must be positive")
    ticks = max(1, round(hedge_delay_us / cfg.dt_us))
    if cfg.hedge_timer and ticks >= cfg.wheel_slots:
        raise ValueError(
            f"hedge_delay_us={hedge_delay_us} is {ticks} ticks but the "
            f"timer wheel has only {cfg.wheel_slots} slots; deepen it "
            "first (FleetConfig.with_hedge_horizon — sweep_grid does this "
            "automatically for its hedge_delays axis)")
    return ticks


def make_params(cfg: FleetConfig, policy_id: int, rate_per_us: float,
                seed: int, slowdown=None, rack_weights=None,
                fail_window: tuple[int, int] | None = None,
                arrival_counts=None,
                hedge_delay_us: float | None = None,
                link_failure=None) -> RunParams:
    from repro.fleetsim.chaos import check_link_failure

    slowdown, rack_weights = check_fabric_arrays(cfg, slowdown, rack_weights)
    arrival_counts = check_arrival_counts(cfg, arrival_counts)
    check_policy_stages(cfg, policy_id)
    delay_ticks = check_hedge_delay(cfg, hedge_delay_us)
    f0, f1 = fail_window if fail_window is not None \
        else (cfg.n_ticks + 1, cfg.n_ticks + 1)
    l0, l1, link_mask = check_link_failure(cfg, link_failure)
    return RunParams(policy_id=jnp.int32(policy_id),
                     rate_per_us=jnp.float32(rate_per_us),
                     seed=jnp.int32(seed),
                     slowdown=jnp.asarray(slowdown, jnp.float32),
                     rack_weights=jnp.asarray(rack_weights, jnp.float32),
                     fail_from_tick=jnp.int32(f0),
                     fail_until_tick=jnp.int32(f1),
                     arrival_counts=jnp.asarray(arrival_counts, jnp.int32),
                     hedge_delay_ticks=jnp.int32(delay_ticks),
                     link_from_tick=jnp.int32(l0),
                     link_until_tick=jnp.int32(l1),
                     link_mask=jnp.asarray(link_mask, bool))


def needs_recovery_wipe(cfg: FleetConfig, params: RunParams) -> bool:
    """Whether the program needs the §3.6 recovery wipe: some run's switch
    recovers (``tick == fail_until_tick``) on a tick of the horizon,
    ``0 … n_ticks − 1``.  Without a window (``make_params`` and
    ``sweep_grid`` put it at ``n_ticks + 1``), or with one that recovers
    past the horizon, the wipe is an identity and the program leaves it
    out.  Abstract params (a caller's outer ``jit``, ``ShapeDtypeStruct``
    inputs) cannot tell, and keep it."""
    until = params.fail_until_tick
    if isinstance(until, (jax.core.Tracer, jax.ShapeDtypeStruct)):
        return True
    until = np.asarray(until)
    return bool(np.any((until >= 0) & (until < cfg.n_ticks)))


# ------------------------------------------------------------------ runner --
def _simulate_core(cfg: FleetConfig, params: RunParams,
                   recovery_wipe: bool = True) -> FleetState:
    with jax.named_scope("fleetsim.init"):
        gp = group_pairs_array(cfg.n_servers)
        k_pois, k0 = jax.random.split(jax.random.PRNGKey(params.seed))
        state = init_fleet_state(cfg, k0)
        # the tick's per-run constants; the tick itself is traced inside
        # the scan, under its own stage scopes
        step = build_step(cfg, params, gp)
        if recovery_wipe:
            step = with_recovery_wipe(cfg, params, step)
    with jax.named_scope("fleetsim.draw"):
        ticks = jnp.arange(cfg.n_ticks, dtype=jnp.int32)
        if cfg.arrival == "trace":
            # replayed per-tick arrival counts ride in as the scan xs
            n_raw = params.arrival_counts.astype(jnp.int32)
        else:
            # per-tick Poisson arrival counts, drawn once outside the scan
            n_raw = jax.random.poisson(
                k_pois, params.rate_per_us * cfg.dt_us, (cfg.n_ticks,)
            ).astype(jnp.int32)
    state, _ = jax.lax.scan(step, state, (ticks, n_raw))
    return state


#: the threefry PRNG mode every engine program traces and lowers under —
#: the mode the checked-in goldens were captured with (JAX's default before
#: it flipped ``jax_threefry_partitionable`` to True), pinned so results do
#: not depend on the process-wide flag
THREEFRY_PARTITIONABLE = False


class PinnedPrng:
    """A jitted function whose calls and lowerings run under
    :data:`THREEFRY_PARTITIONABLE` (the flag is part of jit's cache key, so
    a process that flips it globally still reuses the pinned program)."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __call__(self, *args, **kwargs):
        with jax.threefry_partitionable(THREEFRY_PARTITIONABLE):
            return self._jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        with jax.threefry_partitionable(THREEFRY_PARTITIONABLE):
            return self._jitted.lower(*args, **kwargs)


def select_core(cfg: FleetConfig) -> str:
    """The core that runs ``cfg`` on this process's platform.

    ``'fused'`` (:mod:`repro.fleetsim.fused`: the staged tick replayed in
    chunks over a dtype-packed carry, bit-identical to ``'staged'``) on an
    accelerator (TPU or GPU) when the config compiles in no optional
    stage: no coordinator, no hedge timer, no telemetry and no batch
    server.  ``'staged'`` (one ``lax.scan`` over ticks) everywhere else,
    CPU included.  The one place the engine decides its execution path."""
    if jax.default_backend() not in ("tpu", "gpu"):
        return "staged"
    if (cfg.coordinator or cfg.hedge_timer or cfg.telemetry
            or cfg.server_model == "batch"):
        return "staged"
    return "fused"


def run_core(core: str, cfg: FleetConfig, params: RunParams,
             recovery_wipe: bool) -> FleetState:
    """Advance one fabric on ``core`` (:func:`select_core`)."""
    if core == "fused":
        from repro.fleetsim.fused import fused_core

        return fused_core(cfg, params, recovery_wipe)
    return _simulate_core(cfg, params, recovery_wipe)


# One jitted entry per execution shape (core × batch × telemetry), built on
# demand and cached so every caller of the same shape shares one jit cache.
# The compiled programs bake in the registry's branch tables, so each entry
# is additionally keyed on registry.version(): registering a policy after a
# compile forces a retrace with the grown lax.switch table instead of
# silently reusing a stale executable.  The static recovery_wipe is
# needs_recovery_wipe of the concrete params the caller passes.
@functools.lru_cache(maxsize=None)
def _entry(core: str, batch: bool, telemetry: bool):
    def one(cfg, p, wipe):
        state = run_core(core, cfg, p, wipe)
        if telemetry:
            # FleetScope: the trace ring + series accumulators ride out of
            # the program alongside the metrics
            return state.metrics, state.trace, state.series
        return state.metrics

    def run(cfg: FleetConfig, registry_version: int, recovery_wipe: bool,
            params: RunParams):
        if batch:
            return jax.vmap(lambda p: one(cfg, p, recovery_wipe))(params)
        return one(cfg, params, recovery_wipe)

    return PinnedPrng(jax.jit(
        run, static_argnames=("cfg", "registry_version", "recovery_wipe")))


def _is_batched(params: RunParams) -> bool:
    ndim = jnp.ndim(params.policy_id)
    if ndim > 1:
        raise ValueError(
            f"params.policy_id must be scalar (one run) or 1-D (a batched "
            f"sweep grid); got ndim={ndim}")
    return ndim == 1


def simulate(cfg: FleetConfig, params: RunParams, *, shard=None):
    """THE FleetSim entry point: run ``params`` on ``cfg``, fully jitted.

    ``params`` with scalar fields runs one fabric; a leading sweep axis
    runs the whole batch in one vmapped device program.  The core is
    :func:`select_core`'s.  Returns device :class:`Metrics`, or
    ``(metrics, trace, series)`` when ``cfg.telemetry`` compiled FleetScope
    in (decode with :func:`repro.fleetsim.telemetry.decode_run`; the
    metrics are bit-identical either way — telemetry observes, it never
    feeds back).

    ``shard`` (``None`` | device count | :class:`~repro.fleetsim.shard.
    ShardSpec`) lays a *batched* run over a device mesh and returns a
    :class:`~repro.fleetsim.shard.ShardedMetrics`.
    """
    from repro.fleetsim.shard import as_shard, run_sharded

    shard = as_shard(shard)
    batched = _is_batched(params)
    if shard is not None:
        if not batched:
            raise ValueError(
                "shard lays a sweep grid over a device mesh; params must "
                "carry a leading sweep axis (got scalar RunParams)")
        return run_sharded(cfg, params, shard)
    entry = _entry(select_core(cfg), batched, cfg.telemetry)
    return entry(cfg, registry.version(), needs_recovery_wipe(cfg, params),
                 params)


def lower(cfg: FleetConfig, params: RunParams):
    """``jit(...).lower`` for :func:`simulate` on one device — sweep
    harnesses report compile time separately from steady-state wall clock.
    Sharded lowering lives in :func:`repro.fleetsim.shard.lower_sharded`
    (it needs the padded grid plan, not just params)."""
    entry = _entry(select_core(cfg), _is_batched(params), cfg.telemetry)
    return entry.lower(cfg, registry.version(),
                       needs_recovery_wipe(cfg, params), params)
