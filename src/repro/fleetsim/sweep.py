"""User-facing sweep API: policies × loads × seeds (× delays) in one program.

``sweep_grid`` is the fleetsim counterpart of ``simulator.sweep_load``: it
takes a DES-style :class:`ServiceProcess` (or a :class:`ServiceSpec`), builds
the flat configuration grid, and runs the whole grid through one jitted,
vmapped program.  Stragglers and switch failure windows are per-run inputs,
so heterogeneous scenarios ride in the same batch; ``hedge_delays`` adds the
hedge-timer delay as a fourth, *traced* grid axis (the delay/load plane in
one program), and ``shard`` lays the grid out over a device mesh
(:mod:`repro.fleetsim.shard`) so thousand-point grids spread across a pod —
``shard=None`` keeps the exact single-device program.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

from repro.core.workloads import ServiceProcess, load_to_rate
from repro.fleetsim.config import POLICY_IDS, FleetConfig, ServiceSpec
from repro.fleetsim.chaos import check_link_failure
from repro.fleetsim.engine import (
    RunParams,
    check_fabric_arrays,
    check_hedge_delay,
    lower,
    needs_recovery_wipe,
    select_core,
)
from repro.fleetsim.metrics import FleetResult, summarize
from repro.fleetsim.shard import (
    ShardSpec,
    as_shard,
    lower_sharded,
    plan_grid,
)
from repro.fleetsim.spans import compile_counts, compile_events, phase
from repro.fleetsim.state import Metrics
from repro.fleetsim.telemetry import RunTelemetry, decode_run
from repro.fleetsim.telemetry.device import SeriesState, TraceBuffer
from repro.scenarios import registry


@dataclass
class SweepResult:
    results: list[FleetResult]
    wall_clock_s: float
    compile_s: float
    n_configs: int
    simulated_requests: int
    # --- execution layout (recorded so benchmark artifacts distinguish
    # 1-device vmap runs from N-device sharded runs) ---
    n_devices: int = 1
    shard: ShardSpec | None = None
    n_pad: int = 0                   # grid rows added to divide the mesh
    # the core the sweep compiled ('staged' | 'fused',
    # engine.select_core) — perf baselines key on it
    # (tools/check_perf_trend.py)
    backend: str = "staged"
    # grid-aggregate latency histogram (n_racks, hist_bins), merged
    # device-locally + tree-reduced on the mesh (shard.ShardedMetrics)
    grid_hist: np.ndarray | None = field(default=None, repr=False)
    # FleetScope: one decoded RunTelemetry per grid row (same order as
    # results) when the sweep ran with cfg.telemetry; None otherwise
    telemetry: list[RunTelemetry] | None = field(default=None, repr=False)
    # lowered-HLO cost analysis of the compiled sweep program (XLA's
    # estimate for ONE program execution, i.e. the whole batch), when the
    # backend exposes it; None otherwise
    cost_flops: float | None = None
    cost_bytes: float | None = None
    # the raw host Metrics the results were summarized from (leading axis:
    # grid rows, padding stripped) — for bit-identity checks across runs
    metrics: Metrics | None = field(default=None, repr=False)
    # host seconds per phase of the call (repro.fleetsim.spans):
    # params, lower, compile, device, fetch, summarize.  compile_s is
    # lower + compile and wall_clock_s is device
    phases: dict[str, float] = field(default_factory=dict)
    # jaxpr traces, MLIR lowerings, backend compiles (count and seconds)
    # and persistent-cache hits/misses during the call (spans.COUNTERS)
    compile_events: dict[str, float] = field(default_factory=dict)
    # whether the program was lowered with the §3.6 recovery wipe: only
    # when some row's switch recovers inside the horizon
    # (engine.needs_recovery_wipe)
    recovery_wipe: bool = True

    @property
    def simulated_mrps(self) -> float:
        """Simulated request throughput of the sweep itself (aggregate
        requests advanced per wall-clock second, in millions)."""
        return self.simulated_requests / max(self.wall_clock_s, 1e-9) / 1e6

    def select(self, policy: str | None = None,
               load: float | None = None,
               hedge_delay_us: float | None = None) -> list[FleetResult]:
        out = self.results
        if policy is not None:
            out = [r for r in out if r.policy == policy]
        if load is not None:
            out = [r for r in out if abs(r.offered_load - load) < 1e-9]
        if hedge_delay_us is not None:
            out = [r for r in out
                   if abs(r.hedge_delay_us - hedge_delay_us) < 1e-9]
        return out


def compiled_cost(compiled) -> tuple[float | None, float | None]:
    """Pull ``(flops, bytes accessed)`` out of a compiled program's
    ``cost_analysis()`` — best effort: backends that expose nothing yield
    ``None``s rather than failing the sweep."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None, None
    if not isinstance(ca, dict):
        return None, None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(nbytes) if nbytes is not None else None)


def _as_spec(service) -> ServiceSpec:
    if isinstance(service, ServiceSpec):
        return service
    if isinstance(service, ServiceProcess):
        return ServiceSpec.from_process(service)
    raise TypeError(f"service must be ServiceSpec or ServiceProcess, "
                    f"got {type(service).__name__}")


def rack_skew(cfg: FleetConfig, hot_rack_weight: float = 1.0,
              straggler_rack_mult: float = 1.0,
              ) -> tuple[np.ndarray, np.ndarray]:
    """Build ``(rack_weights, slowdown)`` for the canonical skew scenario:
    rack 0 receives ``hot_rack_weight``× the per-rack arrival share of the
    others, and every server in the *last* rack executes
    ``straggler_rack_mult``× slower.  Both default to 1.0 (no skew)."""
    weights = np.ones(cfg.n_racks, np.float32)
    weights[0] = hot_rack_weight
    slowdown = np.ones((cfg.n_racks, cfg.n_servers), np.float32)
    slowdown[-1, :] = straggler_rack_mult
    return weights, slowdown.reshape(-1)


def _grid_inputs(service, policies, loads, seeds, cfg, slowdown,
                 rack_weights, fail_window_ticks, link_failure,
                 resize_arrival_lanes, hedge_delays, shard, cfg_kw):
    """Check a sweep's arguments and build its inputs: the stage-complete
    config, the grid rows, the rate per load, the batched ``RunParams``
    and the shard layout."""
    spec = _as_spec(service)
    if cfg is None:
        cfg = FleetConfig(service=spec, **cfg_kw)
    else:
        if cfg_kw:
            raise ValueError("pass either cfg or cfg overrides, not both")
        if cfg.service != spec:
            raise ValueError("cfg.service disagrees with the service argument")
    if cfg.arrival != "poisson":
        raise ValueError("sweep_grid sweeps Poisson load grids; run trace "
                         "scenarios through repro.scenarios (run_scenarios)")
    if not policies or not loads or not seeds:
        raise ValueError("sweep_grid needs at least one policy, load, and "
                         "seed (got "
                         f"{len(policies)}×{len(loads)}×{len(seeds)})")
    for p in policies:
        if p not in POLICY_IDS:
            raise ValueError(f"unknown policy {p!r}; have {list(POLICY_IDS)}")
    # compile in the optional pipeline stages the policy set needs (a set
    # needing neither leaves cfg — and its compiled program — untouched)
    cfg = cfg.with_policy_stages(policies)
    if hedge_delays:
        if not any(registry.needs_hedge_timer(p) for p in policies):
            raise ValueError(
                "hedge_delays sweeps the hedge_timer stage's delay, but no "
                f"policy in {policies} uses that stage")
        cfg = cfg.with_hedge_horizon(max(hedge_delays))
    delays: list[float | None] = list(hedge_delays) if hedge_delays \
        else [None]

    rates = {ld: load_to_rate(ld, spec, cfg.n_servers_total, cfg.n_workers)
             for ld in loads}
    if resize_arrival_lanes:
        cfg = cfg.with_arrival_headroom(max(rates.values()))

    slowdown, rack_weights = check_fabric_arrays(cfg, slowdown, rack_weights)

    grid = [(p, ld, s, hd) for p in policies for ld in loads for s in seeds
            # the delay axis only multiplies policies that read the delay
            for hd in (delays if registry.needs_hedge_timer(p) else [None])]
    g = len(grid)
    f0, f1 = fail_window_ticks if fail_window_ticks is not None \
        else (cfg.n_ticks + 1, cfg.n_ticks + 1)
    l0, l1, link_mask = check_link_failure(cfg, link_failure)
    params = RunParams(
        policy_id=np.asarray([POLICY_IDS[p] for p, *_ in grid], np.int32),
        rate_per_us=np.asarray([rates[ld] for _, ld, _, _ in grid],
                               np.float32),
        seed=np.asarray([s for _, _, s, _ in grid], np.int32),
        slowdown=np.broadcast_to(slowdown,
                                 (g, cfg.n_servers_total)).copy(),
        rack_weights=np.broadcast_to(rack_weights, (g, cfg.n_racks)).copy(),
        fail_from_tick=np.full(g, f0, np.int32),
        fail_until_tick=np.full(g, f1, np.int32),
        arrival_counts=np.zeros((g, 0), np.int32),
        hedge_delay_ticks=np.asarray(
            [check_hedge_delay(cfg, hd) for *_, hd in grid], np.int32),
        link_from_tick=np.full(g, l0, np.int32),
        link_until_tick=np.full(g, l1, np.int32),
        link_mask=np.broadcast_to(link_mask,
                                  (g, cfg.n_servers_total)).copy(),
    )
    params = jax.tree.map(lambda a: jax.numpy.asarray(a), params)

    shard_spec = as_shard(shard)
    if cfg.telemetry and shard_spec is not None:
        raise ValueError(
            "telemetry sweeps cannot shard (per-device trace rings have no "
            "merged chronological order); drop shard= or cfg.telemetry")
    return cfg, grid, rates, params, shard_spec


def sweep_grid(
    service,
    policies: list[str],
    loads: list[float],
    seeds: list[int],
    cfg: FleetConfig | None = None,
    slowdown: np.ndarray | None = None,
    rack_weights: np.ndarray | None = None,
    fail_window_ticks: tuple[int, int] | None = None,
    link_failure=None,
    resize_arrival_lanes: bool = True,
    hedge_delays: list[float] | None = None,
    shard: ShardSpec | int | None = None,
    **cfg_kw,
) -> SweepResult:
    """Run every (policy, load, seed[, hedge delay]) combination in one
    jitted program.

    ``slowdown`` (shape ``(n_racks * n_servers,)`` or ``(n_racks,
    n_servers)``) injects stragglers into every run; ``rack_weights``
    (shape ``(n_racks,)``) skews the arrival mix toward hot racks (see
    :func:`rack_skew` for the canonical one-hot-rack / one-straggler-rack
    scenario); ``fail_window_ticks`` darkens the fabric over ``[t0, t1)``
    ticks and wipes its soft state at recovery, for all runs;
    ``link_failure`` (a :class:`repro.fleetsim.chaos.LinkFailure`) kills
    the named server/rack links over its window, for all runs.
    ``resize_arrival_lanes=False`` keeps ``cfg.max_arrivals`` exactly as
    given (pinned array shapes — e.g. golden scenarios) instead of applying
    Poisson headroom for the hottest load.

    ``hedge_delays`` adds a *traced* hedge-delay axis
    (``RunParams.hedge_delay_ticks``): at least one policy in the set must
    use the ``hedge_timer`` stage, the timer wheel is deepened to the
    largest delay automatically, and every hedge-policy result row records
    its ``hedge_delay_us``.  The axis only multiplies policies that
    actually read the delay — a policy without the ``hedge_timer`` hook
    keeps its single row (reported with ``hedge_delay_us=0``) instead of
    running per-delay duplicates.  ``shard`` (``None`` | device count |
    ``ShardSpec``)
    spreads the grid over a device mesh via :mod:`repro.fleetsim.shard`;
    ``None`` compiles the exact single-device program.  The core is
    :func:`~repro.fleetsim.engine.select_core`'s for the stage-complete
    config, and ``SweepResult.backend`` records it.

    Returns host-side results plus wall-clock accounting (compile time
    reported separately so sweep cost is judged on the steady-state
    number): ``phases`` holds the host seconds of the call's ``params``,
    ``lower``, ``compile``, ``device``, ``fetch`` and ``summarize`` phases,
    each also a ``fleetsim.<phase>`` profiler span, and ``compile_events``
    the compile counters the call added (:mod:`repro.fleetsim.spans`).
    """
    phases: dict[str, float] = {}
    counts_before = compile_counts()
    with phase(phases, "params"):
        cfg, grid, rates, params, shard_spec = _grid_inputs(
            service, policies, loads, seeds, cfg, slowdown, rack_weights,
            fail_window_ticks, link_failure, resize_arrival_lanes,
            hedge_delays, shard, cfg_kw)
    g = len(grid)
    with phase(phases, "lower"):
        if shard_spec is None:
            args = (params,)
            lowered = lower(cfg, params)
        else:
            plan = plan_grid(params, shard_spec)
            args = (plan.params, plan.mask)
            lowered = lower_sharded(cfg, plan)
    with phase(phases, "compile"):
        compiled = lowered.compile()
    with phase(phases, "device"):
        out = jax.block_until_ready(compiled(*args))
    grid_hist = tel_state = None
    if shard_spec is not None:
        metrics, grid_hist = out
        n_devices, n_pad = plan.mesh.size, plan.n_pad
    else:
        n_devices, n_pad = 1, 0
        if cfg.telemetry:
            metrics, *tel_state = out
        else:
            metrics = out

    with phase(phases, "fetch"):
        if grid_hist is not None:
            metrics = jax.tree.map(lambda a: a[:g], metrics)
            grid_hist = np.asarray(jax.device_get(grid_hist))
        metrics = jax.device_get(metrics)
        if tel_state is not None:
            tel_state = jax.device_get(tel_state)
    with phase(phases, "summarize"):
        cost_flops, cost_bytes = compiled_cost(compiled)
        telemetry = None
        if tel_state is not None:
            trace, series = tel_state
            telemetry = [
                decode_run(cfg,
                           TraceBuffer(count=trace.count[i],
                                       data=trace.data[i]),
                           SeriesState(*(np.asarray(a)[i] for a in series)))
                for i in range(g)]
        if grid_hist is None:
            # unsharded fallback: same aggregate, reduced on host (the
            # device program stays the exact pre-shard one)
            grid_hist = np.asarray(metrics.hist).sum(axis=0)
        results = []
        for i, (p, ld, s, hd) in enumerate(grid):
            one = jax.tree.map(lambda a: a[i], metrics)
            # policies that never arm the wheel report delay 0, not the
            # config default a hedge co-policy happened to compile in
            hd_report = hd if registry.needs_hedge_timer(p) else 0.0
            results.append(summarize(cfg, one, policy=p, load=ld,
                                     rate_per_us=rates[ld], seed=s,
                                     hedge_delay_us=hd_report))
    return SweepResult(
        results=results,
        wall_clock_s=phases["device"],
        compile_s=phases["lower"] + phases["compile"],
        n_configs=g,
        simulated_requests=sum(r.n_arrivals for r in results),
        n_devices=n_devices,
        shard=shard_spec,
        n_pad=n_pad,
        backend=select_core(cfg),
        grid_hist=grid_hist,
        telemetry=telemetry,
        cost_flops=cost_flops,
        cost_bytes=cost_bytes,
        metrics=metrics,
        phases=phases,
        compile_events=compile_events(counts_before),
        recovery_wipe=needs_recovery_wipe(cfg, params),
    )
