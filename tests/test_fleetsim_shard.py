"""ShardSweep: mesh-sharded sweeps must equal the unsharded vmap exactly.

The acceptance contracts of the shard layer (``repro.fleetsim.shard``):

* ``shard=None`` routes to the untouched unsharded batch program, and a
  1-device :class:`ShardSpec` exercises the real ``shard_map`` path with
  results identical to the vmap — both run in-process on any host;
* on a 2-"device" CPU host (``XLA_FLAGS=
  --xla_force_host_platform_device_count=2``, forced in a subprocess so
  this suite's own jax backend is untouched) a sharded sweep of a grid
  that does NOT divide the device count is **bit-identical** to the
  unsharded run: every counter exact, every histogram equal, and the
  psum-merged ``grid_hist`` equal to the host-side sum;
* padding repeats the last (valid) row and the mask strips it from every
  result — unit-tested over non-divisible grid sizes via ``pad_params``;
* the hedge delay is a traced sweep axis: the same delay traced equals the
  static-config run bit-for-bit, different delays change the tail, and a
  delay beyond the static wheel horizon is rejected at params time.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.fleetsim import (  # noqa: E402
    FleetConfig,
    ServiceSpec,
    ShardSpec,
    make_params,
    sweep_grid,
)
from repro.fleetsim.shard import as_shard, pad_params  # noqa: E402
from repro.fleetsim.validate import shard_equivalence  # noqa: E402
from repro.scenarios import Scenario, SweepSpec  # noqa: E402

SVC = ServiceSpec.exponential(25.0)


def small_cfg(**kw):
    kw.setdefault("n_servers", 4)
    kw.setdefault("n_workers", 8)
    kw.setdefault("n_ticks", 1_500)
    kw.setdefault("service", SVC)
    return FleetConfig(**kw)


# ------------------------------------------------------------- ShardSpec ----
def test_shard_spec_json_roundtrip():
    s = ShardSpec(devices=4, axis="grid")
    assert ShardSpec.from_json(json.loads(json.dumps(s.to_json()))) == s
    assert ShardSpec.from_json({}) == ShardSpec()


def test_shard_spec_rejects_bad_input():
    with pytest.raises(ValueError):
        ShardSpec(devices=-1)
    with pytest.raises(ValueError):
        ShardSpec(axis="")
    with pytest.raises(ValueError):
        ShardSpec.from_json({"device": 2})  # misspelled key
    with pytest.raises(ValueError):
        ShardSpec(devices=4096).resolve_devices()  # more than visible


def test_as_shard_normalization():
    assert as_shard(None) is None
    assert as_shard(2) == ShardSpec(devices=2)
    assert as_shard(True) == ShardSpec()
    assert as_shard(False) is None
    assert as_shard(ShardSpec(devices=3)) == ShardSpec(devices=3)
    with pytest.raises(TypeError):
        as_shard("grid")


# --------------------------------------------------------------- padding ----
@pytest.mark.parametrize("g,n_shards", [(3, 2), (5, 4), (7, 3), (4, 4),
                                        (1, 2), (6, 1)])
def test_pad_params_covers_non_divisible_grids(g, n_shards):
    cfg = small_cfg()
    base = make_params(cfg, policy_id=2, rate_per_us=0.05, seed=0)
    params = jax.tree.map(
        lambda a: np.broadcast_to(np.asarray(a),
                                  (g,) + np.shape(a)).copy(), base)
    padded, mask, n_pad = pad_params(params, n_shards)
    assert n_pad == (-g) % n_shards
    assert padded.policy_id.shape[0] == g + n_pad
    assert (g + n_pad) % n_shards == 0
    assert mask.sum() == g and bool(mask[:g].all())
    if n_pad:
        assert not bool(mask[g:].any())
        # padding repeats the last (valid) row
        last = np.asarray(params.seed[-1])
        np.testing.assert_array_equal(
            np.asarray(padded.seed[g:]),
            np.broadcast_to(last, (n_pad,) + last.shape))


def test_pad_params_rejects_empty_grid():
    cfg = small_cfg()
    base = make_params(cfg, policy_id=2, rate_per_us=0.05, seed=0)
    empty = jax.tree.map(
        lambda a: np.zeros((0,) + np.shape(a), np.asarray(a).dtype), base)
    with pytest.raises(ValueError):
        pad_params(empty, 2)


# ------------------------------------------- 1-device shard_map == vmap -----
def test_one_device_shard_matches_vmap():
    """A 1-device mesh runs the genuine shard_map program on any host;
    its results must match the plain vmap cell-for-cell."""
    cfg = small_cfg()
    kw = dict(policies=["baseline", "netclone"], loads=[0.3, 0.7],
              seeds=[0], cfg=cfg)
    plain = sweep_grid(SVC, **kw)
    sharded = sweep_grid(SVC, shard=ShardSpec(devices=1), **kw)
    assert plain.n_devices == 1 and sharded.shard == ShardSpec(devices=1)
    assert len(plain.results) == len(sharded.results) == 4
    for a, b in zip(plain.results, sharded.results):
        assert a == b
    np.testing.assert_array_equal(plain.grid_hist, sharded.grid_hist)


# -------------------------------------------------- traced hedge delay ------
def test_traced_hedge_delay_matches_static():
    """hedge_delay_us as a sweep axis: the traced value equals the
    static-config program bit-for-bit, and a different delay genuinely
    changes the run."""
    cfg = small_cfg(n_ticks=2_500)
    static = sweep_grid(SVC, policies=["hedge"], loads=[0.3], seeds=[0],
                        cfg=cfg)
    swept = sweep_grid(SVC, policies=["hedge"], loads=[0.3], seeds=[0],
                       cfg=cfg, hedge_delays=[50.0, 75.0])
    assert [r.hedge_delay_us for r in swept.results] == [50.0, 75.0]
    # the config's own delay is 75 → the traced-75 cell is the same run
    assert swept.results[1] == static.results[0]
    assert swept.results[0] != swept.results[1]
    # earlier hedges fire more duplicates before the original returns
    assert swept.results[0].n_hedges_cancelled \
        <= swept.results[1].n_hedges_cancelled


def test_hedge_delay_axis_only_multiplies_hedge_policies():
    """Non-hedge policies ignore the delay, so per-delay duplicates of
    them would waste device time and report a delay they never used: the
    axis must expand only for hedge_timer policies (one row, delay 0,
    for the rest)."""
    cfg = small_cfg(n_ticks=1_000)
    sw = sweep_grid(SVC, policies=["netclone", "hedge"], loads=[0.3],
                    seeds=[0], cfg=cfg, hedge_delays=[50.0, 75.0])
    assert sw.n_configs == 3  # netclone x 1 + hedge x 2 delays
    nc = sw.select(policy="netclone")
    assert len(nc) == 1 and nc[0].hedge_delay_us == 0.0
    assert [r.hedge_delay_us for r in sw.select(policy="hedge")] \
        == [50.0, 75.0]


def test_cross_validate_spec_rejects_hedge_delay_axis():
    """The DES hedge policy runs its own fixed delay — a traced delay
    axis has no DES counterpart, so the cross-validator must refuse
    instead of silently comparing an arbitrary delay's row."""
    from repro.fleetsim.validate import cross_validate_spec

    spec = SweepSpec(base=Scenario(servers=4, workers=8, n_ticks=1_000),
                     policies=("hedge",), loads=(0.3,),
                     hedge_delays=(50.0,))
    with pytest.raises(ValueError, match="hedge_delays"):
        cross_validate_spec(spec, n_requests=100)


def test_hedge_delay_axis_needs_hedge_policy():
    with pytest.raises(ValueError, match="hedge_timer"):
        sweep_grid(SVC, policies=["netclone"], loads=[0.3], seeds=[0],
                   cfg=small_cfg(), hedge_delays=[50.0])


def test_hedge_delay_beyond_wheel_is_rejected():
    cfg = small_cfg().with_policy_stages(["hedge"])
    with pytest.raises(ValueError, match="wheel"):
        make_params(cfg, policy_id=6, rate_per_us=0.05, seed=0,
                    hedge_delay_us=10_000.0)
    # …and with_hedge_horizon makes the same delay legal
    deep = cfg.with_hedge_horizon(10_000.0)
    make_params(deep, policy_id=6, rate_per_us=0.05, seed=0,
                hedge_delay_us=10_000.0)


def test_with_hedge_horizon_is_noop_when_covered():
    cfg = small_cfg().with_policy_stages(["hedge"])
    assert cfg.with_hedge_horizon(10.0) is cfg
    assert small_cfg().with_hedge_horizon(9e9) == small_cfg()  # stage off


# -------------------------------- 2 forced host devices, golden equality ----
_TWO_DEVICE_SCRIPT = r"""
import numpy as np
from repro.fleetsim import ServiceSpec, ShardSpec
from repro.fleetsim.validate import shard_equivalence
from repro.scenarios import Scenario, SweepSpec
import jax
assert len(jax.devices()) == 2, jax.devices()

spec = SweepSpec(
    base=Scenario(name="shard-golden", servers=4, workers=8, n_ticks=1500),
    policies=("netclone",), loads=(0.2, 0.5, 0.8), seeds=(0,))
# 3 grid rows over 2 devices: exercises padding + masking too
checks, hist_ok = shard_equivalence(spec, shard=2)
assert len(checks) == 3
for c in checks:
    assert c.ok, c.describe()
    assert c.counters_ok and c.stat_rel == 0.0, c.describe()
assert hist_ok
print("SHARD-GOLDEN-OK")
"""


def test_two_device_sharded_equals_unsharded_golden():
    """The ISSUE's acceptance check: on a CPU host split into 2 XLA
    devices, a sharded sweep of a non-divisible grid is identical to the
    unsharded vmap — counters exact, stats exact, psum-merged grid_hist
    equal to the host-side sum (needs a fresh process: the forced device
    count must precede jax backend init)."""
    out = subprocess.run(
        [sys.executable, "-c", _TWO_DEVICE_SCRIPT], text=True,
        capture_output=True, timeout=600,
        cwd=str(Path(__file__).parent.parent),
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert out.returncode == 0 and "SHARD-GOLDEN-OK" in out.stdout, \
        out.stdout + out.stderr


# ----------------------------------------------- SweepSpec integration ------
def test_sweepspec_shard_equivalence_one_device():
    """shard_equivalence through the declarative SweepSpec path (1-device
    mesh, so it runs anywhere), including the hedge-delay axis."""
    spec = SweepSpec(
        base=Scenario(name="se", servers=4, workers=8, n_ticks=1_200),
        policies=("baseline", "hedge"), loads=(0.4,), seeds=(0,),
        hedge_delays=(60.0,))
    checks, hist_ok = shard_equivalence(spec, shard=1)
    assert hist_ok and len(checks) == 2
    assert all(c.ok for c in checks)


def test_trace_sweep_rejects_shard():
    from repro.scenarios import TraceArrival

    spec = SweepSpec(
        base=Scenario(name="t", servers=4, workers=8, n_ticks=8,
                      arrival=TraceArrival(counts=(1, 0, 2, 1))),
        policies=("netclone",), shard=ShardSpec(devices=1))
    with pytest.raises(ValueError, match="Poisson"):
        spec.run_fleetsim()
