"""FleetSim static configuration.

Everything in :class:`FleetConfig` is a *compile-time* constant: it fixes the
array shapes of the fleet state and is passed to ``jax.jit`` as a static
argument.  Per-run knobs that vary across a sweep (policy, offered rate, seed,
straggler factors, failure window) are traced values, so one compiled program
serves the whole policy × load × seed grid under ``vmap``.
"""

from __future__ import annotations

import math
from collections.abc import Mapping
from dataclasses import dataclass, replace

from repro.scenarios import registry
from repro.scenarios.service import (  # noqa: F401  (re-exported API)
    SERVICE_BIMODAL,
    SERVICE_EXPONENTIAL,
    SERVICE_LLM,
    SERVICE_PARETO,
    ServiceSpec,
)


class _PolicyIdView(Mapping):
    """Live ``name → id`` view of the unified policy registry.

    Registering a policy (``repro.scenarios.registry.register``) makes it
    appear here immediately — and duplicate names or ids raise at
    registration instead of silently overwriting the reverse map.
    """

    def __getitem__(self, name: str) -> int:
        return registry.policy_id_map()[name]

    def __iter__(self):
        return iter(registry.policy_id_map())

    def __len__(self):
        return len(registry.policy_id_map())

    def __repr__(self):
        return repr(registry.policy_id_map())


class _PolicyNameView(Mapping):
    """Live ``id → name`` reverse view of the registry."""

    def __getitem__(self, policy_id: int) -> str:
        return registry.policy_name_map()[policy_id]

    def __iter__(self):
        return iter(registry.policy_name_map())

    def __len__(self):
        return len(registry.policy_name_map())

    def __repr__(self):
        return repr(registry.policy_name_map())


POLICY_IDS = _PolicyIdView()
POLICY_NAMES = _PolicyNameView()

# Builtin ids — derived from the registry at import so they cannot drift
# from the registrations in core.policies; kept as module constants for
# call sites and notebooks that want a concrete int.
POLICY_BASELINE = POLICY_IDS["baseline"]
POLICY_CCLONE = POLICY_IDS["c-clone"]
POLICY_NETCLONE = POLICY_IDS["netclone"]
POLICY_RACKSCHED = POLICY_IDS["racksched"]
POLICY_NCRS = POLICY_IDS["netclone+racksched"]
POLICY_LAEDGE = POLICY_IDS["laedge"]
POLICY_HEDGE = POLICY_IDS["hedge"]


@dataclass(frozen=True)
class FleetConfig:
    """Shapes + calibrated latency constants of one simulated fabric.

    Latency constants default to the DES's :class:`NetworkCosts` /
    :class:`SwitchCosts` so the two engines are directly comparable.

    ``n_racks == 1`` is the original single-ToR testbed and is guaranteed
    bit-identical to it (same PRNG draws, same op order — enforced by the
    golden test in ``tests/test_fleetsim_fabric.py``).  ``n_racks > 1``
    models a 2-tier fabric: per-rack ToR switches under one spine that
    assigns fabric-global REQ_IDs, aggregates per-rack load, and hosts the
    filter table for inter-rack clone pairs (§3.7's multi-switch story).
    ``n_servers`` is then *per rack*.
    """

    n_racks: int = 1
    n_servers: int = 6
    n_workers: int = 15
    # client machines (receiver threads); 0 → scale with the fabric
    # (2 per rack, the DES's 2-clients-per-6-server-rack testbed ratio), so
    # multi-rack sweeps aren't silently receiver-bound
    n_clients: int = 0
    # FCFS slots per server.  Ring buffers make capacity nearly free (no
    # per-tick op scales with it), so the default is deep enough that beyond-
    # saturation runs build DES-like unbounded-queue latency instead of
    # shedding copies through overflow (which is still counted when hit).
    queue_cap: int = 512
    max_arrivals: int = 12       # arrival lanes per tick (Poisson is clipped)
    max_responses: int = 32      # response lanes per tick (clipping counted)
    dt_us: float = 1.0
    n_ticks: int = 50_000
    warmup_frac: float = 0.1
    service: ServiceSpec = ServiceSpec.exponential(25.0)
    # arrival-process kind: "poisson" draws per-tick counts device-side from
    # the run's rate + seed; "trace" replays the per-tick count sequence
    # passed in ``RunParams.arrival_counts`` (see repro.scenarios.arrival)
    arrival: str = "poisson"
    # switch tables.  The prototype's 2×2^17 slots bound collisions for
    # millions of in-flight ids; a simulated rack keeps O(100) fingerprints
    # live, so far smaller tables preserve the collision behaviour while
    # keeping the per-tick scatter (and its operand copy) cheap.
    n_filter_tables: int = 2
    n_filter_slots: int = 2 ** 10
    # client-side first-response fingerprints: sized above the worst-case
    # in-flight population (n_servers × (workers + queue_cap)) so collisions
    # that evict a live entry (n_dedup_evicted) stay rare even past saturation
    n_dedup_slots: int = 2 ** 13
    # transport/processing constants (µs) — match simulator.NetworkCosts
    link_us: float = 0.5
    server_overhead_us: float = 1.0
    client_rx_us: float = 0.68
    client_tx_us: float = 0.15
    pipeline_pass_us: float = 0.4
    # one-way client↔spine / spine↔rack-switch hop (µs); only paid when the
    # fabric actually has a spine tier (n_racks > 1)
    spine_hop_us: float = 0.5
    # ---- optional pipeline stages (repro.fleetsim.stages) ----------------
    # Static compile-out flags: with a flag off the stage contributes ZERO
    # traced ops (the jitted program is the one the flag-less engine built,
    # so the n_racks=1 goldens stay bit-identical); with it on, the stage's
    # sub-state joins FleetState and policies registered with the matching
    # hook (registry coordinator / hedge_timer) become runnable.  Scenario
    # / sweep builders flip these automatically from the policy set.
    #
    # coordinator: LÆDGE-style CPU node hanging off the top switch — one
    # FCFS CPU that spends coord_cpu_us on every request, duplicate
    # dispatch and response packet (the paper's coordinator bottleneck),
    # with a ring of requests waiting for an idle server.
    coordinator: bool = False
    coordinator_cap: int = 2 ** 11      # waiting-request ring slots
    coord_cpu_us: float = 1.5           # CPU per packet — matches the DES
    # hedge_timer: fixed-depth timer wheel ((n_slots, wheel_width) entries)
    # firing delayed duplicates hedge_delay_us after arrival unless the
    # first response beat the timer.  Width 0 sizes to max_arrivals (every
    # arrival lane can arm); slots 0 sizes to the delay horizon + 1.
    hedge_timer: bool = False
    hedge_delay_us: float = 75.0        # ≈p95 service — matches HedgePolicy
    hedge_wheel_slots: int = 0
    hedge_wheel_width: int = 0
    # telemetry (FleetScope, repro.fleetsim.telemetry): device-resident
    # request-event ring buffer + windowed time-series, compiled out exactly
    # like coordinator/hedge_timer when the flag is off.  Telemetry is an
    # observer — it draws no PRNG traffic and feeds nothing back, so a
    # telemetry-on run keeps every Metrics counter bit-identical.
    telemetry: bool = False
    trace_cap: int = 2 ** 15            # ring-buffer records (flight recorder)
    window_ticks: int = 1_000           # time-series window length (ticks)
    # server_model: "fcfs" (the original per-worker FCFS ring) or "batch"
    # (ServeSim, repro.fleetsim.llmserve: continuous-batching slots —
    # admit-into-free-slot, all busy slots progress every tick, complete on
    # exhausted demand).  Static like coordinator/hedge_timer: with "fcfs"
    # the batch stage contributes zero traced ops and the goldens stay
    # bit-identical; with "batch" the FCFS ring is never traced and the
    # queue-length piggyback reports waiting-for-a-slot depth, so routing
    # policies route on batch pressure.
    server_model: str = "fcfs"
    # decode slots per server under server_model="batch" (0 → n_workers)
    batch_slots: int = 0
    # batching slowdown: a slot running with k busy neighbours progresses at
    # 1 / (1 + batch_coupling × (k-1)/(B-1)) per tick.  0 (default) models
    # memory-bound decode (batch size is nearly free — slots independent,
    # matching serve.engine.DecodeReplica); 1 halves per-slot progress at
    # full occupancy (compute-bound prefill-heavy regime).
    batch_coupling: float = 0.0
    # response-filter backend: "vectorized" (one scatter/tick, default on
    # every platform), "scan" (exact lane-sequential switch_jax.filter
    # semantics), "pallas" (kernels.fingerprint_filter — the SMEM filter
    # kernel), or "tickfuse" (kernels.tickfuse — StateT + filter fused in
    # ONE kernel).  The two kernels run only where a config names them, and
    # their tables must fit the kernel's SMEM (checked below)
    filter_backend: str = "vectorized"
    # log-spaced latency histogram (≈6% bin resolution over 1 µs … 2 s)
    hist_bins: int = 256
    hist_lo_us: float = 1.0
    hist_growth: float = 1.06

    def __post_init__(self):
        if self.n_racks < 1:
            raise ValueError("n_racks must be at least 1")
        if self.n_clients == 0:
            object.__setattr__(self, "n_clients", 2 * self.n_racks)
        if self.n_clients < 1:
            raise ValueError("n_clients must be >= 1 (or 0 to auto-scale)")
        if self.n_filter_slots & (self.n_filter_slots - 1):
            raise ValueError("n_filter_slots must be a power of two")
        if self.n_dedup_slots & (self.n_dedup_slots - 1):
            raise ValueError("n_dedup_slots must be a power of two")
        if self.filter_backend not in ("vectorized", "scan", "pallas",
                                       "tickfuse"):
            raise ValueError(f"unknown filter_backend {self.filter_backend!r}")
        if self.filter_table_size > 2 ** 31 - 1:
            raise ValueError(
                f"{self.n_racks + 1} filter groups x {self.n_filter_tables} "
                f"x {self.n_filter_slots} slots = {self.filter_table_size} "
                "do not fit int32 flat indices (at most 2^31 - 1)")
        if self.filter_backend in ("pallas", "tickfuse"):
            self._check_kernel_smem()
        if self.arrival not in ("poisson", "trace"):
            raise ValueError(f"unknown arrival kind {self.arrival!r}")
        if self.n_servers < 2:
            raise ValueError("fleetsim requires at least two servers per rack")
        # req ids ride in float32 payload lanes; keep them exactly
        # representable (REQ_ID ≤ n_ticks × max_arrivals < 2^24)
        if self.n_ticks * self.max_arrivals >= 2 ** 24:
            raise ValueError("n_ticks × max_arrivals must stay below 2^24 "
                             "(REQ_IDs are carried in float32 payloads)")
        if self.coordinator and self.coordinator_cap < 1:
            raise ValueError("coordinator_cap must be >= 1")
        if self.coord_cpu_us < 0:
            raise ValueError("coord_cpu_us must be >= 0")
        if self.server_model not in ("fcfs", "batch"):
            raise ValueError(f"unknown server_model {self.server_model!r} "
                             "(expected 'fcfs' or 'batch')")
        if self.batch_slots < 0:
            raise ValueError("batch_slots must be >= 0 (0 → n_workers)")
        if self.batch_coupling < 0:
            raise ValueError("batch_coupling must be >= 0")
        if self.telemetry:
            if self.trace_cap < 1:
                raise ValueError("trace_cap must be >= 1")
            if not 1 <= self.window_ticks <= self.n_ticks:
                raise ValueError("window_ticks must be in [1, n_ticks] "
                                 f"(got {self.window_ticks} with n_ticks="
                                 f"{self.n_ticks})")
        if self.hedge_timer:
            if self.hedge_delay_us <= 0:
                raise ValueError("hedge_delay_us must be positive")
            if 0 < self.hedge_wheel_slots <= self.hedge_delay_ticks:
                raise ValueError(
                    f"hedge_wheel_slots must exceed the delay horizon "
                    f"({self.hedge_delay_ticks} ticks) so an armed entry "
                    "cannot alias a pending slot")

    def _check_kernel_smem(self) -> None:
        """The switch kernels hold one fabric's table stack in SMEM; refuse
        a fabric whose tables cannot fit instead of failing in the TPU
        compiler (or silently running another backend)."""
        from repro.kernels.tickfuse import SMEM_LIMIT_BYTES, smem_bytes

        if self.filter_backend == "tickfuse":
            servers, lanes = self.n_servers_total, self.max_responses
        else:  # pallas: no StateT, lanes padded to the kernel's 256 block
            servers, lanes = 0, -(-self.max_responses // 256) * 256
        need = smem_bytes((self.n_racks + 1) * self.n_filter_tables,
                          self.n_filter_slots, servers, lanes)
        if need > SMEM_LIMIT_BYTES:
            raise ValueError(
                f"filter_backend={self.filter_backend!r} needs {need} B of "
                f"TPU SMEM for {self.n_racks} racks x {self.n_filter_tables}"
                f" x {self.n_filter_slots}-slot filter tables, over the "
                f"kernel limit SMEM_LIMIT_BYTES={SMEM_LIMIT_BYTES}; use "
                "filter_backend='vectorized' or smaller tables")

    @property
    def n_groups(self) -> int:
        """GrpT entries per rack switch (ordered pairs of local servers)."""
        return self.n_servers * (self.n_servers - 1)

    @property
    def filter_table_size(self) -> int:
        """Slots of the fabric's filter tables: every rack's group and the
        spine's (``FabricSwitch.filter_tables``)."""
        return (self.n_racks + 1) * self.n_filter_tables * self.n_filter_slots

    @property
    def filter_table_shape(self) -> tuple[int, int]:
        """Shape of ``FabricSwitch.filter_tables``: the flat tables in rows
        of the TPU's 128 lanes (of one table, where tables are smaller)."""
        row = min(128, self.n_filter_slots)
        return self.filter_table_size // row, row

    @property
    def n_servers_total(self) -> int:
        return self.n_racks * self.n_servers

    @property
    def spine_extra_us(self) -> float:
        """Round-trip latency added by the spine tier every request pays
        under a 2-tier fabric (two extra link hops + two pipeline passes);
        zero when the fabric is a single ToR."""
        if self.n_racks == 1:
            return 0.0
        return 2.0 * (self.spine_hop_us + self.pipeline_pass_us)

    @property
    def interrack_extra_us(self) -> float:
        """Additional one-way detour paid by the remote copy of an
        inter-rack clone pair (spine → remote rack switch and back up)."""
        if self.n_racks == 1:
            return 0.0
        return 2.0 * (self.spine_hop_us + self.pipeline_pass_us)

    @property
    def hedge_delay_ticks(self) -> int:
        """The hedge delay quantized to ticks (at least one — a same-tick
        hedge would race its own original)."""
        return max(1, round(self.hedge_delay_us / self.dt_us))

    @property
    def wheel_slots(self) -> int:
        """Resolved timer-wheel depth: explicit, or the delay horizon + 1
        (an entry armed at tick t fires exactly at t + delay, and the slot
        it lands in drained one full rotation earlier)."""
        return self.hedge_wheel_slots or self.hedge_delay_ticks + 1

    @property
    def wheel_width(self) -> int:
        """Resolved per-slot entry budget: explicit, or ``max_arrivals``
        (every arrival lane of one tick can arm without drops)."""
        return self.hedge_wheel_width or self.max_arrivals

    @property
    def n_slots(self) -> int:
        """Resolved decode slots per server under ``server_model="batch"``:
        explicit ``batch_slots``, or ``n_workers`` (each worker lane becomes
        one continuous-batching slot, keeping the state shapes shared)."""
        return self.batch_slots or self.n_workers

    @property
    def n_windows(self) -> int:
        """Time-series windows per run (the last window may be partial)."""
        return -(-self.n_ticks // self.window_ticks)

    @property
    def coord_sends_per_tick(self) -> int:
        """Copies the coordinator can deliver to the servers in one tick.
        Each copy leaves the CPU after a pass of its own, so at most
        ``floor(dt / coord_cpu_us) + 1`` leave inside one tick; and no
        tick creates more than two copies per arrival lane plus one per
        response lane.  The smaller bound is exact: no copy waits past
        its tick."""
        made = 2 * self.max_arrivals + self.max_responses
        if self.coord_cpu_us <= 0:
            return made
        return min(made, math.floor(self.dt_us / self.coord_cpu_us) + 1)

    @property
    def duration_us(self) -> float:
        return self.n_ticks * self.dt_us

    @property
    def warmup_us(self) -> float:
        return self.warmup_frac * self.duration_us

    def with_arrival_headroom(self, max_rate_per_us: float) -> "FleetConfig":
        """Size the per-tick arrival lanes so Poisson clipping is negligible
        at the hottest point of a sweep (≈6σ above the mean count)."""
        lam = max_rate_per_us * self.dt_us
        lanes = int(math.ceil(lam + 6.0 * math.sqrt(max(lam, 1e-9)) + 2.0))
        return replace(self, max_arrivals=max(4, lanes))

    def with_hedge_horizon(self, max_delay_us: float) -> "FleetConfig":
        """Deepen the hedge timer wheel to cover per-run (traced) delays up
        to ``max_delay_us`` (``RunParams.hedge_delay_ticks`` is a sweep
        axis, but the wheel's depth is a compile-time shape).  No-op when
        the stage is compiled out or the resolved wheel already covers the
        horizon — so delay-less sweeps keep their exact program."""
        if not self.hedge_timer:
            return self
        if max_delay_us <= 0:
            raise ValueError("max_delay_us must be positive")
        horizon = max(1, round(max_delay_us / self.dt_us))
        if self.wheel_slots > horizon:
            return self
        return replace(self, hedge_wheel_slots=horizon + 1)

    def with_policy_stages(self, policies) -> "FleetConfig":
        """Compile in the pipeline stages the given policy names need
        (coordinator / hedge_timer registry hooks).  A config whose policy
        set needs neither is returned unchanged — and therefore produces
        the exact bit-identical program it always did."""
        need_coord = any(registry.needs_coordinator(p) for p in policies)
        need_hedge = any(registry.needs_hedge_timer(p) for p in policies)
        cfg = self
        if need_coord and not cfg.coordinator:
            cfg = replace(cfg, coordinator=True)
        if need_hedge and not cfg.hedge_timer:
            cfg = replace(cfg, hedge_timer=True)
        return cfg
