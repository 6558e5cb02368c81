"""FleetSim — the fully-jitted, vmapped, device-resident cluster simulator.

Where ``repro.core.simulator`` replays one (policy, load, seed) configuration
at a time in Python, FleetSim keeps the entire 2-tier fabric — per-rack
switch soft state under a spine tier that places and filters inter-rack
clones, per-server FCFS queues and workers, client receiver threads — in
JAX arrays,
advances it with one ``lax.scan``, and sweeps thousands of configurations in
a single ``vmap``-ped device program — or, with ``repro.fleetsim.shard``,
lays the sweep grid out over a device mesh so each device owns a contiguous
slab of configurations (``shard_map`` over the ``'grid'`` axis, with an
honest single-device fallback).

The one entry point is ``simulate(cfg, params, *, shard=None)`` — single
run or vmapped batch (inferred from the params leading axis), sharded over
a device mesh or not.  The core is chosen from what the code can see
(``engine.select_core``: fused TickFuse, ``repro.fleetsim.fused``, on an
accelerator for configs without an optional stage; staged otherwise), and
telemetry is on exactly when ``cfg.telemetry`` compiles it in — see
``docs/api.md``.  The NetClone data-plane semantics are
shared with ``repro.core.switch_jax`` (the same state layout and filter
rules), and results are cross-validated against the DES in
``repro.fleetsim.validate`` / ``tests/test_fleetsim.py``.

``repro.fleetsim.telemetry`` (FleetScope) adds compile-time-optional
observability: a device-resident request-event ring buffer and windowed
time-series, decoded host-side into per-request timelines and
Chrome-trace/CSV exports — see ``docs/observability.md``.

``repro.fleetsim.llmserve`` (ServeSim) adds an LLM-serving workload layer:
model-derived ``llm`` service specs (:func:`llm_service`, roofline decode /
prefill costs) and a continuous-batching server stage selected by the
static ``FleetConfig.server_model="batch"`` flag, cross-validated against
the real-model :class:`repro.serve.engine.DecodeReplica`
(:func:`serve_equivalence`).

See ``docs/architecture.md`` for the layer map (DES ↔ scenarios registry ↔
FleetSim stages ↔ shard layer) and the array-layout tables.
"""

from repro.fleetsim.config import (
    POLICY_IDS,
    POLICY_NAMES,
    FleetConfig,
    ServiceSpec,
)
from repro.fleetsim.engine import (
    RunParams,
    lower,
    make_params,
    select_core,
    simulate,
)
from repro.fleetsim.metrics import FleetResult, summarize
from repro.fleetsim.state import (
    CoordState,
    FabricSwitch,
    FleetState,
    HedgeWheel,
    Metrics,
    init_fleet_state,
)
from repro.fleetsim.shard import (
    GridPlan,
    ShardedMetrics,
    ShardSpec,
    plan_grid,
)
from repro.fleetsim.sweep import SweepResult, rack_skew, sweep_grid
from repro.fleetsim.telemetry import (
    RunTelemetry,
    TelemetrySpec,
    TickSeries,
    TraceEvents,
    decode_run,
    write_run,
)
from repro.fleetsim.validate import (
    CrossCheck,
    ServeCheck,
    ShardCheck,
    cross_check_scenario,
    cross_validate,
    cross_validate_spec,
    serve_equivalence,
    shard_equivalence,
)
from repro.fleetsim.llmserve import (
    decode_step_us,
    llm_service,
    prefill_us,
)

__all__ = [
    "FleetConfig",
    "ServiceSpec",
    "POLICY_IDS",
    "POLICY_NAMES",
    "RunParams",
    "make_params",
    "simulate",
    "lower",
    "select_core",
    "RunTelemetry",
    "TelemetrySpec",
    "TickSeries",
    "TraceEvents",
    "decode_run",
    "write_run",
    "FleetResult",
    "summarize",
    "FabricSwitch",
    "FleetState",
    "CoordState",
    "HedgeWheel",
    "Metrics",
    "init_fleet_state",
    "SweepResult",
    "rack_skew",
    "sweep_grid",
    "ShardSpec",
    "GridPlan",
    "ShardedMetrics",
    "plan_grid",
    "CrossCheck",
    "ServeCheck",
    "ShardCheck",
    "cross_validate",
    "cross_validate_spec",
    "cross_check_scenario",
    "serve_equivalence",
    "shard_equivalence",
    "llm_service",
    "decode_step_us",
    "prefill_us",
]
