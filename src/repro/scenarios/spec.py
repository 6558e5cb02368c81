"""Declarative scenarios: one frozen description, both engines.

A :class:`Scenario` freezes everything that defines an experiment — policy,
load, seed, fabric shape, skew/failure injection, service and arrival
processes — and both engines consume it directly: :meth:`Scenario.run_des`
replays it through the discrete-event simulator, :meth:`Scenario.run_fleetsim`
through the jitted array engine.  Cross-validation becomes
comparison-by-construction: the two runs *cannot* encode the testbed
differently, because there is only one encoding.

:class:`SweepSpec` is the declarative grid (policies × loads × seeds over a
base scenario).  ``policies="registered"`` expands to every policy the
registry can run through both engines at execution time — so registering a
custom policy automatically enters it into every such sweep.

Both round-trip to JSON (``from_file``/``to_file``); bundled files live in
``repro/scenarios/library`` and are resolvable by bare name.  The golden
library scenario reproduces the PR-2 single-ToR golden run bit-identically
(enforced in ``tests/test_scenarios.py``).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, replace
from pathlib import Path

import jax
import numpy as np

from repro.core.workloads import load_to_rate, rate_to_load
from repro.fleetsim.chaos import LinkFailure
from repro.fleetsim.config import FleetConfig
from repro.fleetsim.engine import make_params, simulate
from repro.fleetsim.metrics import FleetResult, summarize
from repro.fleetsim.shard import ShardSpec
from repro.fleetsim.spans import compile_counts, compile_events, phase
from repro.fleetsim.sweep import SweepResult, rack_skew, sweep_grid
from repro.fleetsim.telemetry import RunTelemetry, TelemetrySpec, decode_run
from repro.scenarios import registry
from repro.scenarios.arrival import (
    ArrivalProcess,
    PoissonArrival,
    arrival_from_json,
)
from repro.scenarios.service import ServiceSpec

LIBRARY_DIR = Path(__file__).parent / "library"


@dataclass(frozen=True)
class Scenario:
    """One experiment, declaratively.

    ``load`` is the offered fraction of cluster capacity (Poisson arrivals);
    trace arrivals carry their own schedule and ``load`` is ignored.
    ``queue_cap``/``max_arrivals`` default to the engine's sizing
    (arrival-headroom for Poisson, the trace's max tick count for traces) —
    set them only to pin exact array shapes, as the golden scenario does.
    ``slowdown`` (per-server multipliers, ``racks × servers`` entries)
    overrides the canonical ``straggler_rack_mult`` injection.
    """

    name: str = "scenario"
    policy: str = "netclone"
    load: float = 0.5
    seed: int = 0
    racks: int = 1
    servers: int = 6
    workers: int = 15
    n_ticks: int = 50_000
    service: ServiceSpec = ServiceSpec.exponential(25.0)
    arrival: ArrivalProcess = PoissonArrival()
    hot_rack_weight: float = 1.0
    straggler_rack_mult: float = 1.0
    slowdown: tuple[float, ...] | None = None
    fail_window_ticks: tuple[int, int] | None = None
    # ChaosFuzz failure campaign (repro.fleetsim.chaos): dead links for the
    # named servers/racks over a tick window, in BOTH engines
    link_failure: LinkFailure | None = None
    queue_cap: int | None = None
    max_arrivals: int | None = None
    # ServeSim (repro.fleetsim.llmserve): "batch" swaps the FCFS worker
    # pool for continuous-batching decode slots; batch_slots/batch_coupling
    # mirror the FleetConfig knobs (0 slots → one per worker)
    server_model: str = "fcfs"
    batch_slots: int = 0
    batch_coupling: float = 0.0
    # tick length override (µs).  LLM scenarios pin it to the model's
    # per-token decode cost so one tick is one generated token; None keeps
    # the engine default (or the trace's own dt for trace arrivals, which
    # define their schedule's time base and reject an override here).
    dt_us: float | None = None
    # FleetScope observability (repro.fleetsim.telemetry): None runs the
    # exact telemetry-off program; a spec compiles the trace/series stages in
    telemetry: TelemetrySpec | None = None

    def __post_init__(self):
        # injection windows are validated at spec load: a window hanging
        # past the horizon would otherwise silently truncate (the engines
        # only ever compare tick against the window edges)
        if self.fail_window_ticks is not None:
            f0, f1 = self.fail_window_ticks
            if not 0 <= f0 < f1 <= self.n_ticks:
                raise ValueError(
                    f"fail_window_ticks [{f0}, {f1}) must satisfy 0 <= "
                    f"start < end <= n_ticks={self.n_ticks}; shrink the "
                    "window or raise n_ticks")
        if self.link_failure is not None:
            l0, l1 = self.link_failure.window
            if l1 > self.n_ticks:
                raise ValueError(
                    f"link_failure window [{l0}, {l1}) exceeds "
                    f"n_ticks={self.n_ticks}; shrink start_tick/duration "
                    "or raise n_ticks")
            # fail fast on out-of-range rack/server ids too (one line, at
            # load time — not a gather error from inside a trace)
            self.link_failure.mask(self.racks, self.servers)

    # ------------------------------------------------------------ derived --
    @property
    def n_servers_total(self) -> int:
        return self.racks * self.servers

    def rate_per_us(self, n_ticks: int | None = None) -> float:
        """Offered arrival rate: load-derived for Poisson, the replayed
        sequence's own mean for traces."""
        rate = load_to_rate(self.load, self.service,
                            self.n_servers_total, self.workers)
        return self.arrival.mean_rate_per_us(rate, n_ticks or self.n_ticks)

    def effective_load(self, n_ticks: int | None = None) -> float:
        """Offered load; recomputed from the trace mean for trace runs."""
        if self.arrival.kind == "poisson":
            return self.load
        return rate_to_load(self.rate_per_us(n_ticks), self.service,
                            self.n_servers_total, self.workers)

    # ----------------------------------------------------------- fleetsim --
    def fleet_config(self, **overrides) -> FleetConfig:
        """The jit-static FleetSim configuration this scenario pins down."""
        kw = dict(n_racks=self.racks, n_servers=self.servers,
                  n_workers=self.workers, n_ticks=self.n_ticks,
                  service=self.service, arrival=self.arrival.kind)
        if self.arrival.kind == "trace":
            if self.dt_us is not None:
                raise ValueError("dt_us cannot be overridden for trace "
                                 "arrivals; the trace defines its own time "
                                 "base (TraceArrival.dt_us)")
            kw["dt_us"] = self.arrival.dt_us
        elif self.dt_us is not None:
            kw["dt_us"] = self.dt_us
        if self.server_model != "fcfs":
            kw["server_model"] = self.server_model
            kw["batch_slots"] = self.batch_slots
            kw["batch_coupling"] = self.batch_coupling
        elif self.batch_slots or self.batch_coupling:
            raise ValueError("batch_slots / batch_coupling only apply to "
                             "server_model='batch'")
        if self.queue_cap is not None:
            kw["queue_cap"] = self.queue_cap
        if self.max_arrivals is not None:
            kw["max_arrivals"] = self.max_arrivals
        kw.update(overrides)
        cfg = FleetConfig(**kw)
        # compile in the optional pipeline stages this policy needs
        # (coordinator / hedge_timer registry hooks); stage-less policies
        # keep the exact config — and compiled program — they always had
        cfg = cfg.with_policy_stages([self.policy])
        if self.max_arrivals is None and "max_arrivals" not in overrides:
            if self.arrival.kind == "trace":
                lanes = max(4, self.arrival.max_count(cfg.n_ticks))
                cfg = replace(cfg, max_arrivals=lanes)
            else:
                cfg = cfg.with_arrival_headroom(self.rate_per_us(cfg.n_ticks))
        if self.telemetry is not None:
            cfg = self.telemetry.apply(cfg)
        return cfg

    def run_params(self, cfg: FleetConfig):
        """Traced per-run inputs for :func:`repro.fleetsim.engine.simulate`."""
        d = registry.get(self.policy)
        if d.policy_id is None:
            raise ValueError(f"policy {self.policy!r} has no array-engine "
                             "id; it can only run through the DES")
        weights, slowdown = rack_skew(cfg, self.hot_rack_weight,
                                      self.straggler_rack_mult)
        if self.slowdown is not None:
            slowdown = np.asarray(self.slowdown, np.float32).reshape(-1)
        return make_params(
            cfg, d.policy_id, self.rate_per_us(cfg.n_ticks), self.seed,
            slowdown=slowdown, rack_weights=weights,
            fail_window=self.fail_window_ticks,
            arrival_counts=self.arrival.tick_counts(cfg.n_ticks),
            link_failure=self.link_failure)

    def fleet_metrics(self, **cfg_overrides):
        """Run the array engine; returns ``(cfg, raw device Metrics)``."""
        cfg = self.fleet_config(**cfg_overrides)
        out = jax.block_until_ready(simulate(cfg, self.run_params(cfg)))
        return cfg, out[0] if cfg.telemetry else out

    def run_fleetsim(self, **cfg_overrides) -> FleetResult:
        cfg, m = self.fleet_metrics(**cfg_overrides)
        return summarize(cfg, jax.device_get(m), policy=self.policy,
                         load=self.effective_load(cfg.n_ticks),
                         rate_per_us=self.rate_per_us(cfg.n_ticks),
                         seed=self.seed)

    def run_traced(self, **cfg_overrides
                   ) -> tuple[FleetResult, RunTelemetry]:
        """Run the array engine with FleetScope on and decode the trace.

        A scenario without a ``telemetry`` spec gets the default one forced
        on for this run; the result's counters are bit-identical either way
        (telemetry observes, it never feeds back).  Export the bundle with
        :func:`repro.fleetsim.telemetry.write_run`."""
        sc = self if self.telemetry is not None and self.telemetry.enabled \
            else replace(self, telemetry=TelemetrySpec())
        cfg = sc.fleet_config(**cfg_overrides)
        m, trace, series = jax.block_until_ready(
            simulate(cfg, sc.run_params(cfg)))
        m, trace, series = jax.device_get((m, trace, series))
        result = summarize(cfg, m, policy=self.policy,
                           load=self.effective_load(cfg.n_ticks),
                           rate_per_us=self.rate_per_us(cfg.n_ticks),
                           seed=self.seed)
        return result, decode_run(cfg, trace, series)

    # ---------------------------------------------------------------- DES --
    def run_des(self, n_requests: int | None = None,
                n_ticks: int | None = None, **run_kw):
        """Replay through the discrete-event simulator (single ToR)."""
        from repro.core.simulator import Simulator

        if self.racks != 1:
            raise ValueError("the DES models a single ToR; scenario has "
                             f"racks={self.racks}")
        if self.server_model != "fcfs":
            raise ValueError(
                "the DES models FCFS worker pools; batch-server scenarios "
                "cross-validate against the DecodeReplica oracle instead "
                "(repro.fleetsim.llmserve.oracle.serve_equivalence)")
        if (self.hot_rack_weight != 1.0 or self.straggler_rack_mult != 1.0
                or self.slowdown is not None):
            raise ValueError("the DES does not model slowdown / rack-skew "
                             "injection")
        svc = self.service.to_process()
        sim = Simulator(self.policy, svc, n_servers=self.servers,
                        n_workers=self.workers, seed=self.seed)
        nt = n_ticks or self.n_ticks
        dt = self.arrival.dt_us if self.arrival.kind == "trace" else 1.0
        if self.fail_window_ticks is not None:
            f0, f1 = self.fail_window_ticks
            sim.schedule_switch_failure(f0 * dt, f1 * dt)
        if self.link_failure is not None:
            l0, l1 = self.link_failure.window
            dead = np.nonzero(self.link_failure.mask(1, self.servers))[0]
            sim.schedule_link_failure(l0 * dt, l1 * dt, dead)
        if self.arrival.kind == "trace":
            return sim.run(arrival=self.arrival, n_ticks=nt, **run_kw)
        if n_requests is None:
            n_requests = int(np.clip(self.rate_per_us() * nt, 1_000, 50_000))
        # non-trace processes answer through their own des_times (for the
        # stock PoissonArrival this is draw-identical to arrival=None)
        return sim.run(offered_load=self.load, n_requests=n_requests,
                       arrival=self.arrival, n_ticks=nt, **run_kw)

    # --------------------------------------------------------------- JSON --
    def to_json(self) -> dict:
        d = {
            "name": self.name, "policy": self.policy, "load": self.load,
            "seed": self.seed, "racks": self.racks, "servers": self.servers,
            "workers": self.workers, "n_ticks": self.n_ticks,
            "service": self.service.to_json(),
            "arrival": self.arrival.to_json(),
            "hot_rack_weight": self.hot_rack_weight,
            "straggler_rack_mult": self.straggler_rack_mult,
        }
        if self.slowdown is not None:
            d["slowdown"] = list(self.slowdown)
        if self.fail_window_ticks is not None:
            d["fail_window_ticks"] = list(self.fail_window_ticks)
        if self.link_failure is not None:
            d["link_failure"] = self.link_failure.to_json()
        if self.queue_cap is not None:
            d["queue_cap"] = self.queue_cap
        if self.max_arrivals is not None:
            d["max_arrivals"] = self.max_arrivals
        if self.server_model != "fcfs":
            d["server_model"] = self.server_model
            if self.batch_slots:
                d["batch_slots"] = self.batch_slots
            if self.batch_coupling:
                d["batch_coupling"] = self.batch_coupling
        if self.dt_us is not None:
            d["dt_us"] = self.dt_us
        if self.telemetry is not None:
            d["telemetry"] = self.telemetry.to_json()
        return d

    _JSON_KEYS = ("name", "policy", "load", "seed", "racks", "servers",
                  "workers", "n_ticks", "hot_rack_weight",
                  "straggler_rack_mult", "queue_cap", "max_arrivals",
                  "server_model", "batch_slots", "batch_coupling", "dt_us",
                  "service", "arrival", "slowdown", "fail_window_ticks",
                  "link_failure", "telemetry")

    @classmethod
    def from_json(cls, d: dict) -> "Scenario":
        unknown = sorted(set(d) - set(cls._JSON_KEYS))
        if unknown:
            # files are the API: a misspelled knob must not silently run a
            # different experiment than the one written down
            raise ValueError(f"unknown scenario keys {unknown}; "
                             f"valid: {sorted(cls._JSON_KEYS)}")
        kw = {k: d[k] for k in cls._JSON_KEYS
              if k in d and k not in ("service", "arrival", "slowdown",
                                      "fail_window_ticks", "link_failure",
                                      "telemetry")}
        if "service" in d:
            kw["service"] = ServiceSpec.from_json(d["service"])
        kw["arrival"] = arrival_from_json(d.get("arrival"))
        if d.get("slowdown") is not None:
            kw["slowdown"] = tuple(float(v) for v in d["slowdown"])
        if d.get("fail_window_ticks") is not None:
            kw["fail_window_ticks"] = tuple(d["fail_window_ticks"])
        if d.get("link_failure") is not None:
            kw["link_failure"] = LinkFailure.from_json(d["link_failure"])
        if d.get("telemetry") is not None:
            kw["telemetry"] = TelemetrySpec.from_json(d["telemetry"])
        return cls(**kw)

    def to_file(self, path) -> Path:
        path = Path(path)
        path.write_text(json.dumps(self.to_json(), indent=1) + "\n")
        return path

    @classmethod
    def from_file(cls, path) -> "Scenario":
        return cls.from_json(json.loads(resolve(path).read_text()))


@dataclass(frozen=True)
class SweepSpec:
    """A declarative policy × load × seed (× hedge-delay) grid over a base
    scenario.

    ``policies="registered"`` (the default) expands *at run time* to every
    policy registered for both engines, so custom registrations enter every
    sweep without touching the spec.  Empty ``loads`` means the base
    scenario's single load.  ``hedge_delays`` adds the hedge-timer delay as
    a traced grid axis (needs a ``hedge_timer`` policy in the set), and
    ``shard`` lays the whole grid out over a device mesh
    (:class:`repro.fleetsim.shard.ShardSpec`; ``None`` keeps the exact
    single-device vmap program) — both Poisson-grid features, rejected for
    trace replays.
    """

    base: Scenario
    policies: tuple[str, ...] | str = "registered"
    loads: tuple[float, ...] = ()
    seeds: tuple[int, ...] = (0,)
    hedge_delays: tuple[float, ...] = ()
    shard: ShardSpec | None = None

    def resolved_policies(self) -> list[str]:
        if self.policies == "registered":
            return registry.two_engine_names()
        return list(self.policies)

    def resolved_loads(self) -> list[float]:
        return list(self.loads) or [self.base.load]

    def scenarios(self) -> list[Scenario]:
        """The expanded grid, one frozen Scenario per cell."""
        return [
            replace(self.base, policy=p, load=ld, seed=s,
                    name=f"{self.base.name}[{p}@{ld:g}#s{s}]")
            for p in self.resolved_policies()
            for ld in self.resolved_loads()
            for s in self.seeds
        ]

    def run_fleetsim(self, **cfg_overrides) -> SweepResult:
        """Run the whole grid through the array engine — one vmapped device
        program for Poisson grids, per-scenario runs (shared compile) for
        trace replays."""
        base = self.base
        if base.arrival.kind == "poisson":
            cfg = base.fleet_config(**cfg_overrides)
            weights, slowdown = rack_skew(cfg, base.hot_rack_weight,
                                          base.straggler_rack_mult)
            if base.slowdown is not None:
                slowdown = np.asarray(base.slowdown, np.float32).reshape(-1)
            # a pinned max_arrivals (explicit in the scenario or the
            # overrides) fixes the array shapes — don't re-derive headroom
            pinned = (base.max_arrivals is not None
                      or "max_arrivals" in cfg_overrides)
            return sweep_grid(base.service, self.resolved_policies(),
                              self.resolved_loads(), list(self.seeds),
                              cfg=cfg, slowdown=slowdown,
                              rack_weights=weights,
                              fail_window_ticks=base.fail_window_ticks,
                              link_failure=base.link_failure,
                              resize_arrival_lanes=not pinned,
                              hedge_delays=list(self.hedge_delays) or None,
                              shard=self.shard)
        if self.shard is not None or self.hedge_delays:
            raise ValueError("shard / hedge_delays are Poisson-grid "
                             "features (one vmapped program); trace "
                             "replays run per-scenario")
        if len(self.resolved_loads()) > 1:
            # a trace IS the offered schedule: each load cell would run the
            # same configuration and waste device time on duplicate rows
            raise ValueError("trace-arrival sweeps ignore `load`; sweep "
                             "policies/seeds only (got loads="
                             f"{self.resolved_loads()})")
        return run_scenarios(self.scenarios(), **cfg_overrides)

    # --------------------------------------------------------------- JSON --
    def to_json(self) -> dict:
        d = {"base": self.base.to_json(),
             "policies": (self.policies if isinstance(self.policies, str)
                          else list(self.policies)),
             "loads": list(self.loads), "seeds": list(self.seeds)}
        if self.hedge_delays:
            d["hedge_delays"] = list(self.hedge_delays)
        if self.shard is not None:
            d["shard"] = self.shard.to_json()
        return d

    _JSON_KEYS = ("base", "policies", "loads", "seeds", "hedge_delays",
                  "shard")

    @classmethod
    def from_json(cls, d: dict) -> "SweepSpec":
        unknown = sorted(set(d) - set(cls._JSON_KEYS))
        if unknown:
            raise ValueError(f"unknown sweep keys {unknown}; "
                             f"valid: {sorted(cls._JSON_KEYS)}")
        pol = d.get("policies", "registered")
        shard = d.get("shard")
        return cls(base=Scenario.from_json(d["base"]),
                   policies=pol if isinstance(pol, str) else tuple(pol),
                   loads=tuple(d.get("loads", ())),
                   seeds=tuple(d.get("seeds", (0,))),
                   hedge_delays=tuple(d.get("hedge_delays", ())),
                   shard=None if shard is None else ShardSpec.from_json(shard))

    def to_file(self, path) -> Path:
        path = Path(path)
        path.write_text(json.dumps(self.to_json(), indent=1) + "\n")
        return path

    @classmethod
    def from_file(cls, path) -> "SweepSpec":
        return cls.from_json(json.loads(resolve(path).read_text()))


def run_scenarios(scenarios: list[Scenario], **cfg_overrides) -> SweepResult:
    """Run heterogeneous scenarios through the array engine one by one.

    Scenarios sharing a static config reuse one compiled program, and
    compilation is timed separately from the steady-state runs (matching
    ``sweep_grid``'s accounting, so MRPS numbers are comparable between
    Poisson grids and trace replays).  The call's host phases are
    ``sweep_grid``'s, summed over scenarios: ``compile_s`` is ``lower`` +
    ``compile`` and ``wall_clock_s`` is ``device``.  A program shared by
    scenarios keeps the §3.6 recovery wipe if any of them recovers inside
    the horizon (``SweepResult.recovery_wipe``: any program did)."""
    from repro.fleetsim.engine import lower, needs_recovery_wipe

    phases: dict[str, float] = {}
    counts_before = compile_counts()
    with phase(phases, "params"):
        prepared = []
        for sc in scenarios:
            cfg = sc.fleet_config(**cfg_overrides)
            prepared.append((sc, cfg, sc.run_params(cfg)))
    # scenarios sharing a static config reuse one compiled program,
    # lowered on the params of one that needs the wipe, if any does
    lowering: dict = {}
    for sc, cfg, params in prepared:
        wipe = needs_recovery_wipe(cfg, params)
        if cfg not in lowering or (wipe and not lowering[cfg][1]):
            lowering[cfg] = (params, wipe)
    compiled: dict = {}
    for cfg, (params, _) in lowering.items():
        with phase(phases, "lower"):
            lowered = lower(cfg, params)
        with phase(phases, "compile"):
            compiled[cfg] = lowered.compile()
    results = []
    for sc, cfg, params in prepared:
        with phase(phases, "device"):
            m = jax.block_until_ready(compiled[cfg](params))
        with phase(phases, "fetch"):
            m = jax.device_get(m[0] if cfg.telemetry else m)
        with phase(phases, "summarize"):
            results.append(summarize(
                cfg, m, policy=sc.policy,
                load=sc.effective_load(cfg.n_ticks),
                rate_per_us=sc.rate_per_us(cfg.n_ticks), seed=sc.seed))
    return SweepResult(results=results, wall_clock_s=phases.get("device", 0.0),
                       compile_s=(phases.get("lower", 0.0)
                                  + phases.get("compile", 0.0)),
                       n_configs=len(scenarios),
                       simulated_requests=sum(r.n_arrivals for r in results),
                       phases=phases,
                       compile_events=compile_events(counts_before),
                       recovery_wipe=any(w for _, w in lowering.values()))


# ------------------------------------------------------------------ library --
def scenario_library() -> dict[str, Path]:
    """Bundled scenario/sweep files, by bare name."""
    return {p.stem: p for p in sorted(LIBRARY_DIR.glob("*.json"))}


def resolve(path) -> Path:
    """A filesystem path, or the bare name of a bundled library file."""
    p = Path(path)
    if p.exists():
        return p
    lib = scenario_library()
    if str(path) in lib:
        return lib[str(path)]
    raise FileNotFoundError(
        f"{path!r} is neither a file nor a bundled scenario "
        f"(bundled: {sorted(lib)})")


def load_any(path) -> Scenario | SweepSpec:
    """Load a scenario or sweep file, whichever the JSON describes."""
    d = json.loads(resolve(path).read_text())
    if "base" in d or "policies" in d:
        return SweepSpec.from_json(d)
    return Scenario.from_json(d)
