"""ShardSweep: the sweep grid axis laid out over a device mesh.

The unsharded engine vmaps a whole policy × load × seed (× hedge-delay)
grid onto *one* device.  This module is the multi-device execution path —
``simulate(cfg, params, shard=...)`` — the same
grid is laid out on a 1-D :class:`jax.sharding.Mesh` (axis ``'grid'``) and
run under ``shard_map``, so each device owns a **contiguous slab of
configurations** and advances it with the exact per-configuration program
the unsharded engine compiles — configurations are embarrassingly parallel,
so the only cross-device traffic is the final histogram merge.

Three pieces make that honest:

* **padding + masking** (:func:`plan_grid`) — a grid whose size is not
  divisible by the device count is padded by repeating its last row (a
  *valid* configuration, so every lane of the program stays well-defined);
  a boolean mask rides along and padded rows are excluded from reductions
  and stripped before results reach the host;
* **device-local metric reduction** (:data:`ShardedMetrics.grid_hist`) —
  each device sums the latency histograms of its own (masked) slab
  locally, then the per-device partials merge with one
  ``jax.lax.psum`` over the mesh axis (XLA lowers this to a tree/ring
  all-reduce), so the grid-aggregate latency distribution never takes the
  ``grid × racks × bins`` host-gather detour;
* **an honest single-device fallback** — ``shard=None`` routes to the
  unsharded batch engine untouched, compiling the exact program the repo
  always compiled (golden-tested), and a 1-device :class:`ShardSpec` still
  exercises the real ``shard_map`` path so CPU CI covers it without forced
  devices.

The multi-device program is testable anywhere: ``XLA_FLAGS=
--xla_force_host_platform_device_count=N`` splits a CPU host into N
devices (``benchmarks/run.py --devices N`` sets this up, and
``tests/test_fleetsim_shard.py`` pins sharded == unsharded equality on 2
forced host devices).  Sharded results are bitwise-identical per
configuration — each cell runs the identical per-configuration program —
so the equivalence check in ``validate.py`` demands exact counters and
histogram equality (see :func:`repro.fleetsim.validate.shard_equivalence`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.fleetsim.config import FleetConfig
from repro.fleetsim.engine import (
    PinnedPrng,
    RunParams,
    needs_recovery_wipe,
    run_core,
    select_core,
)
from repro.fleetsim.state import Metrics
from repro.scenarios import registry

#: default mesh-axis name the grid is sharded over
GRID_AXIS = "grid"


@dataclass(frozen=True)
class ShardSpec:
    """How a sweep grid is laid out over devices.

    ``devices=0`` (the default) takes every visible device; an explicit
    count takes the first ``devices`` of ``jax.devices()`` — useful both
    for pinning layouts in scenario files and for CPU hosts split with
    ``--xla_force_host_platform_device_count``.  ``axis`` names the mesh
    axis (purely cosmetic unless composed into a larger mesh).

    Round-trips through JSON (:meth:`to_json` / :meth:`from_json`) so a
    :class:`repro.scenarios.SweepSpec` can carry its sharding layout.
    """

    devices: int = 0
    axis: str = GRID_AXIS

    def __post_init__(self):
        if self.devices < 0:
            raise ValueError("ShardSpec.devices must be >= 0 (0 = all)")
        if not self.axis or not isinstance(self.axis, str):
            raise ValueError("ShardSpec.axis must be a non-empty string")

    def resolve_devices(self) -> list:
        """The concrete device list this spec runs on (validated)."""
        devs = jax.devices()
        n = self.devices or len(devs)
        if n > len(devs):
            raise ValueError(
                f"ShardSpec wants {n} devices but only {len(devs)} are "
                f"visible; on CPU hosts set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n} before jax "
                f"initializes (benchmarks/run.py --devices does this)")
        return devs[:n]

    def mesh(self) -> Mesh:
        """The 1-D device mesh with the grid axis."""
        return Mesh(np.asarray(self.resolve_devices()), (self.axis,))

    # --------------------------------------------------------------- JSON --
    def to_json(self) -> dict:
        return {"devices": self.devices, "axis": self.axis}

    @classmethod
    def from_json(cls, d: dict) -> "ShardSpec":
        unknown = sorted(set(d) - {"devices", "axis"})
        if unknown:
            raise ValueError(f"unknown shard keys {unknown}; "
                             "valid: ['axis', 'devices']")
        return cls(devices=int(d.get("devices", 0)),
                   axis=str(d.get("axis", GRID_AXIS)))


def as_shard(shard) -> ShardSpec | None:
    """Normalize a ``shard`` argument: ``None`` (unsharded), a device
    count, or a :class:`ShardSpec`."""
    if shard is None or isinstance(shard, ShardSpec):
        return shard
    if isinstance(shard, bool):
        return ShardSpec() if shard else None
    if isinstance(shard, int):
        return ShardSpec(devices=shard)
    raise TypeError(f"shard must be None, bool, int, or ShardSpec; "
                    f"got {type(shard).__name__}")


class GridPlan(NamedTuple):
    """A padded, mesh-ready grid layout (host-side plan, nothing traced)."""

    mesh: Mesh            # 1-D device mesh over the grid axis
    params: RunParams     # leading axis padded to a multiple of mesh.size
    mask: jax.Array       # (padded,) bool — True for real grid rows
    n_grid: int           # true grid size (rows the caller asked for)
    n_pad: int            # rows appended to divide evenly


class ShardedMetrics(NamedTuple):
    """Per-configuration metrics plus the mesh-reduced aggregate."""

    metrics: Metrics      # every leaf has leading axis n_grid (pad stripped)
    # (n_racks, hist_bins) — the grid-total latency histogram, merged
    # device-locally and tree-reduced across the mesh (never host-gathered)
    grid_hist: jax.Array


def grid_size(params: RunParams) -> int:
    """Leading-axis length of a batched :class:`RunParams`."""
    return int(params.policy_id.shape[0])


def pad_params(params: RunParams,
               n_shards: int) -> tuple[RunParams, jax.Array, int]:
    """Pad the grid axis to a multiple of ``n_shards`` and build the mask.

    Padding repeats the **last row** — a valid configuration, so the padded
    lanes run a well-defined program (their results are masked out of
    reductions and sliced away before the host sees them).  Returns
    ``(padded_params, mask, n_pad)`` with ``mask`` True on real rows.
    """
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    g = grid_size(params)
    if g < 1:
        raise ValueError("cannot shard an empty grid")
    n_pad = (-g) % n_shards
    if n_pad:
        params = jax.tree.map(
            lambda a: jnp.concatenate(
                [jnp.asarray(a),
                 jnp.repeat(jnp.asarray(a)[-1:], n_pad, axis=0)]),
            params)
    else:
        params = jax.tree.map(jnp.asarray, params)
    mask = jnp.arange(g + n_pad) < g
    return params, mask, n_pad


def plan_grid(params: RunParams, spec: ShardSpec) -> GridPlan:
    """Build the mesh for ``spec`` and pad ``params`` to divide it."""
    mesh = spec.mesh()
    g = grid_size(params)
    params, mask, n_pad = pad_params(params, mesh.size)
    return GridPlan(mesh=mesh, params=params, mask=mask,
                    n_grid=g, n_pad=n_pad)


# ---------------------------------------------------------------- runner ----
# Like engine._entry's programs, the cache is keyed on registry.version()
# (post-compile policy registrations must retrace the grown switch tables)
# and additionally on the mesh and the core (engine.select_core), so layout
# and core changes each get their own executable, and on whether the §3.6
# recovery wipe is compiled in (engine.needs_recovery_wipe of the padded
# grid).
def _simulate_sharded(cfg: FleetConfig, registry_version: int,
                      mesh: Mesh, params: RunParams, mask: jax.Array,
                      core: str, recovery_wipe: bool = True):
    axis = mesh.axis_names[0]

    def slab(p: RunParams, m: jax.Array):
        # each device advances its contiguous slab with the per-config
        # program of the unsharded engine — no cross-device traffic …
        met = jax.vmap(
            lambda q: run_core(core, cfg, q, recovery_wipe).metrics)(p)
        # … except the histogram merge: mask out padding, reduce the slab
        # locally, then one psum (tree/ring all-reduce) across the mesh
        keep = m.astype(met.hist.dtype)
        local = (met.hist * keep[:, None, None]).sum(axis=0)
        return met, jax.lax.psum(local, axis)

    spec_g = PartitionSpec(axis)
    # the psum's result is replicated by construction, which is what the
    # P() out_spec declares; the varying-manual-axes checker is off because
    # the while loop inside jax.random.poisson has no rule for it (nothing
    # here relies on inferred replication — the only collective is the psum)
    return jax.shard_map(slab, mesh=mesh, in_specs=(spec_g, spec_g),
                         out_specs=(spec_g, PartitionSpec()),
                         check_vma=False)(params, mask)


_simulate_sharded_jit = PinnedPrng(jax.jit(
    _simulate_sharded, static_argnames=("cfg", "registry_version", "mesh",
                                        "core", "recovery_wipe")))


def lower_sharded(cfg: FleetConfig, plan: GridPlan):
    """``jit(...).lower`` for the sharded runner (sweeps report compile
    time separately from steady-state wall clock, like ``engine.lower``)."""
    return _simulate_sharded_jit.lower(cfg, registry.version(), plan.mesh,
                                       plan.params, plan.mask,
                                       core=select_core(cfg),
                                       recovery_wipe=needs_recovery_wipe(
                                           cfg, plan.params))


def _strip_pad(plan: GridPlan, metrics: Metrics) -> Metrics:
    return jax.tree.map(lambda a: a[:plan.n_grid], metrics)


def run_sharded(cfg: FleetConfig, params: RunParams,
                spec: ShardSpec) -> ShardedMetrics:
    """The mesh-sharded execution path behind ``simulate(..., shard=...)``.

    Pads the grid onto ``spec``'s mesh and runs the ``shard_map`` program
    on :func:`~repro.fleetsim.engine.select_core`'s core; per-configuration
    results are bitwise-identical to the unsharded run (enforced by
    ``validate.shard_equivalence`` and ``tests/test_fleetsim_shard.py``).
    """
    if cfg.telemetry:
        raise ValueError(
            "telemetry is not supported on the sharded runner (the trace "
            "ring would be sharded too and its per-device rings cannot be "
            "merged into one chronological stream); run the traced config "
            "unsharded, or drop cfg.telemetry for the sharded sweep")
    plan = plan_grid(params, spec)
    met, grid_hist = _simulate_sharded_jit(cfg, registry.version(),
                                           plan.mesh, plan.params, plan.mask,
                                           core=select_core(cfg),
                                           recovery_wipe=needs_recovery_wipe(
                                               cfg, plan.params))
    return ShardedMetrics(metrics=_strip_pad(plan, met), grid_hist=grid_hist)
