"""EngineOptions: one knob object selecting FleetSim's execution path.

The redesigned entry point is

    repro.fleetsim.simulate(cfg, params, *, options=EngineOptions(...))

and every way of running the engine — single run or vmapped batch (inferred
from the ``params`` leading axis), staged or fused (TickFuse) backend,
mesh-sharded or single-device, with or without FleetScope telemetry —
is a field here instead of a separate ``simulate_*`` function.  Invalid
combinations fail at *options construction or resolution time* with the
same clear errors the old entry points raised, rather than deep inside a
trace.

Backends
--------
``'staged'``
    The PR-4 staged pipeline: one ``lax.scan`` over ticks, state carried
    unpacked.  Supports every policy, telemetry, and sharding.
``'fused'``
    TickFuse (``repro.fleetsim.fused``): the same staged tick, chunked
    ``K`` ticks per outer scan step with the integer state dtype-packed at
    chunk boundaries.  The switch response path runs as the TickFuse
    Pallas kernel only when the config asks for it
    (``FleetConfig.filter_backend="tickfuse"``); no backend selects it.
    **Bit-identical** to ``'staged'`` on the non-stage policy matrix
    (baseline / c-clone / netclone / racksched / netclone+racksched) — the
    chunks replay the exact staged tick ops in the exact order, and integer
    pack/round-trips are exact.  Stage policies (laedge / hedge) and
    telemetry are not supported; ``'auto'`` falls back for them.
``'auto'``
    ``'fused'`` on an accelerator backend (TPU/GPU) when the config has
    no optional stage and no telemetry, ``'staged'`` otherwise — CPU
    included, where the staged program is the measured-fastest path (see
    docs/architecture.md, "TickFuse megakernel").

The JSON form (:meth:`to_json` / :meth:`from_json`) is the strict-keyed
``engine`` sub-object scenario and sweep files carry, mirroring ``shard``.
"""

from __future__ import annotations

from dataclasses import dataclass

from repro.fleetsim.shard import ShardSpec, as_shard

#: execution backends selectable via EngineOptions.backend
BACKENDS = ("auto", "fused", "staged")

_TELEMETRY_SHARD_ERROR = (
    "telemetry is not supported on the sharded runner (the trace ring would "
    "be sharded too and its per-device rings cannot be merged into one "
    "chronological stream); drop shard= or telemetry=")


def _accel_default_backend() -> str:
    """What 'auto' resolves to on this process's default jax backend."""
    import jax

    return "fused" if jax.default_backend() in ("tpu", "gpu") else "staged"


@dataclass(frozen=True)
class EngineOptions:
    """How one :func:`repro.fleetsim.simulate` call executes.

    ``backend`` picks staged vs fused (see module docstring); ``shard``
    (``None`` | device count | :class:`ShardSpec`) lays a *batched* run
    over a device mesh; ``telemetry`` returns ``(metrics, trace, series)``
    instead of bare metrics (needs ``cfg.telemetry=True``); ``donate``
    donates the ``params`` buffers to the compiled call (they are consumed
    — reuse of the caller's arrays raises), saving a copy for large grids;
    ``ticks_per_chunk`` sets the fused backend's K (0 → auto).
    """

    backend: str = "auto"
    shard: ShardSpec | None = None
    telemetry: bool = False
    donate: bool = False
    # fused-backend chunk length: K ticks advance per outer scan step with
    # the state packed at chunk boundaries; 0 picks the default (512,
    # clipped to n_ticks).  Results are K-independent (bit-identical): K
    # only moves the pack/unpack points.
    ticks_per_chunk: int = 0

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"valid: {list(BACKENDS)}")
        object.__setattr__(self, "shard", as_shard(self.shard))
        if self.telemetry and self.shard is not None:
            raise ValueError(_TELEMETRY_SHARD_ERROR)
        if self.ticks_per_chunk < 0:
            raise ValueError("ticks_per_chunk must be >= 0 (0 = auto)")

    # ------------------------------------------------------------ resolve --
    def resolve_backend(self, cfg) -> str:
        """The concrete backend ('staged' | 'fused') for ``cfg``.

        ``'fused'`` is validated — optional-stage configs (coordinator /
        hedge_timer) and telemetry raise the clear error here, at the
        options layer; ``'auto'`` falls back to ``'staged'`` for them (and
        on CPU, where the fused path has no native kernel to win with).
        """
        if self.backend == "staged":
            return "staged"
        staged_only = []
        if cfg.coordinator:
            staged_only.append("the coordinator stage (laedge)")
        if cfg.hedge_timer:
            staged_only.append("the hedge_timer stage (hedge)")
        if getattr(cfg, "server_model", "fcfs") == "batch":
            staged_only.append(
                "the batch server stage (server_model='batch')")
        if self.telemetry or cfg.telemetry:
            staged_only.append("telemetry (FleetScope)")
        if self.backend == "fused":
            if staged_only:
                raise ValueError(
                    "backend='fused' does not support "
                    + ", ".join(staged_only)
                    + "; use backend='staged' (or 'auto', which falls back)")
            return "fused"
        # auto
        if staged_only:
            return "staged"
        return _accel_default_backend()

    # --------------------------------------------------------------- JSON --
    def to_json(self) -> dict:
        d: dict = {"backend": self.backend}
        if self.ticks_per_chunk:
            d["ticks_per_chunk"] = self.ticks_per_chunk
        return d

    _JSON_KEYS = ("backend", "ticks_per_chunk")

    @classmethod
    def from_json(cls, d: dict) -> "EngineOptions":
        unknown = sorted(set(d) - set(cls._JSON_KEYS))
        if unknown:
            # files are the API: a misspelled knob must not silently run a
            # different engine than the one written down
            raise ValueError(f"unknown engine keys {unknown}; "
                             f"valid: {sorted(cls._JSON_KEYS)}")
        return cls(backend=str(d.get("backend", "auto")),
                   ticks_per_chunk=int(d.get("ticks_per_chunk", 0)))
