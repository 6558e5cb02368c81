"""ChaosFuzz: failure campaigns in both engines + the generative fuzz tier.

Contracts pinned here:

* :class:`~repro.fleetsim.chaos.LinkFailure` validates at spec load — bad
  windows, bad targets, and full-fabric wipes fail with one actionable
  line, never a gather error from inside a trace;
* an *inert* link-failure window is value-identical to the pre-chaos
  pipeline (the partition-off bit-identity to the checked-in goldens is
  enforced by ``tests/test_scenarios.py::test_golden_scenario_file_bit_
  identical``, which now runs through the chaos stages);
* active windows drop traffic in BOTH engines and the two agree within
  the documented cross-validation tolerances (the bundled
  ``chaos_partition`` library scenario);
* switch-wipe and straggler injection move per-rack tails, and wipe
  counters reconcile exactly against the trace that drove them;
* :class:`~repro.scenarios.arrival.TraceArrival` replay is exact under
  the fused backend (seeded property sweep);
* the fuzz driver (``repro.scenarios.fuzz``) is deterministic, and a
  deliberately-broken engine yields a *shrunk, replayable* counterexample
  (``-m fuzz``; excluded from the default pytest run via pyproject).
"""

import json
from dataclasses import replace

import numpy as np
import pytest
from _cores import use_core

from repro.core.simulator import Simulator
from repro.core.workloads import ExponentialService
from repro.fleetsim.chaos import LinkFailure, check_link_failure
from repro.fleetsim.validate import cross_check_scenario
from repro.scenarios import fuzz as fuzz_mod
from repro.scenarios.arrival import TraceArrival
from repro.scenarios.service import ServiceSpec
from repro.scenarios.spec import Scenario, load_any


def _sc(**kw):
    base = dict(name="chaos-test", policy="netclone", load=0.5, seed=3,
                racks=1, servers=4, workers=8, n_ticks=20_000,
                service=ServiceSpec.exponential(25.0))
    base.update(kw)
    return Scenario(**base)


# ------------------------------------------------------- spec validation --
def test_link_failure_rejects_bad_windows_and_targets():
    with pytest.raises(ValueError, match="duration"):
        LinkFailure(start_tick=0, duration=0, servers=(0,))
    with pytest.raises(ValueError, match="at least one"):
        LinkFailure(start_tick=0, duration=10)
    lf = LinkFailure(start_tick=0, duration=10, servers=(7,))
    with pytest.raises(ValueError, match="out of range"):
        lf.mask(1, 4)
    with pytest.raises(ValueError, match="fabric wipe"):
        LinkFailure(start_tick=0, duration=10, servers=(0, 1, 2, 3)).mask(1, 4)


def test_injection_windows_validate_against_n_ticks():
    # satellite: a window hanging past the horizon fails at spec load with
    # one actionable line (not a silent truncation inside the engines)
    with pytest.raises(ValueError, match="exceeds n_ticks=1000"):
        _sc(n_ticks=1000,
            link_failure=LinkFailure(start_tick=900, duration=200,
                                     servers=(0,)))
    with pytest.raises(ValueError, match="n_ticks=1000"):
        _sc(n_ticks=1000, fail_window_ticks=(800, 1200))
    with pytest.raises(ValueError, match="out of range"):
        _sc(link_failure=LinkFailure(start_tick=0, duration=10,
                                     servers=(99,)))


def test_link_failure_json_round_trip_and_strict_keys():
    lf = LinkFailure(start_tick=100, duration=50, racks=(1,), servers=(0,))
    assert LinkFailure.from_json(lf.to_json()) == lf
    with pytest.raises(ValueError, match="unknown"):
        LinkFailure.from_json({"start_tick": 0, "duration": 1,
                               "servers": [0], "racks": [], "oops": 1})
    sc = _sc(link_failure=LinkFailure(start_tick=100, duration=50,
                                      servers=(1,)))
    assert Scenario.from_json(json.loads(json.dumps(sc.to_json()))) == sc


# -------------------------------------------------------- fleetsim engine --
def test_inert_window_is_value_identical():
    # absent failure == explicit None: same params, same result row
    sc = _sc(n_ticks=8_000)
    cfg = sc.fleet_config()
    p_none = replace(sc, link_failure=None).run_params(cfg)
    p_abs = sc.run_params(cfg)
    for a, b in zip(p_none, p_abs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    f0, f1, mask = check_link_failure(cfg, None)
    assert f0 == f1 == cfg.n_ticks + 1 and not mask.any()


def test_fleetsim_partition_drops_and_degrades():
    sc = _sc(link_failure=LinkFailure(start_tick=6_000, duration=8_000,
                                      servers=(2, 3)))
    r_fail = sc.run_fleetsim()
    r_ok = replace(sc, link_failure=None).run_fleetsim()
    assert r_ok.n_link_dropped_req == 0 == r_ok.n_link_dropped_resp
    assert r_fail.n_link_dropped_req > 0
    assert r_fail.n_completed < r_ok.n_completed
    # same arrival stream either way — the failure only eats copies
    assert r_fail.n_arrivals == r_ok.n_arrivals


def test_partition_collapses_dead_rack_only():
    # the partitioned rack's completions collapse; the spine masks remote
    # routes/clones toward it, so the healthy rack's service is untouched
    sc = _sc(racks=2, servers=4, workers=8, n_ticks=16_000,
             link_failure=LinkFailure(start_tick=4_000, duration=8_000,
                                      racks=(1,)))
    r_fail = sc.run_fleetsim()
    r_ok = replace(sc, link_failure=None).run_fleetsim()
    assert r_fail.rack_completed[1] < 0.6 * r_ok.rack_completed[1]
    assert r_fail.rack_completed[0] >= 0.95 * r_ok.rack_completed[0]


# -------------------------------------------------------------- DES engine --
def test_des_link_failure_drops_and_recovers():
    svc = ExponentialService(25.0)
    sim = Simulator("baseline", svc, n_servers=4, n_workers=8, seed=3)
    sim.schedule_link_failure(8_000.0, 14_000.0, [2, 3])
    r = sim.run(offered_load=0.5, n_requests=20_000)
    ref = Simulator("baseline", svc, n_servers=4, n_workers=8, seed=3).run(
        offered_load=0.5, n_requests=20_000)
    assert sim.n_link_dropped_req > 0
    assert r.n_completed < ref.n_completed
    # single-copy baseline: every link-dropped request is a lost request
    assert ref.n_completed - r.n_completed >= 0.9 * sim.n_link_dropped_req
    with pytest.raises(ValueError, match="out of range"):
        sim.schedule_link_failure(0.0, 1.0, [9])
    with pytest.raises(ValueError, match="at least one"):
        sim.schedule_link_failure(0.0, 1.0, [])


def test_hedging_rides_through_partition_in_des():
    # losing a copy on a dead link leaves the hedge timer armed, so the
    # deferred duplicate recovers the request — hedging loses almost
    # nothing.  NetClone's dispatch-time cloning does NOT help here: its
    # switch state goes stale (no responses refresh a dead server), so
    # single-copy sends to a dead-but-idle-looking server are lost exactly
    # like baseline's.  Both behaviours are contracts.
    svc = ExponentialService(25.0)
    lost, dropped = {}, {}
    for pol, kw in (("baseline", {}), ("netclone", {}),
                    ("hedge", {"delay_us": 75.0})):
        ref = Simulator(pol, svc, n_servers=4, n_workers=8, seed=5,
                        **kw).run(offered_load=0.4, n_requests=20_000)
        sim = Simulator(pol, svc, n_servers=4, n_workers=8, seed=5, **kw)
        sim.schedule_link_failure(8_000.0, 16_000.0, [3])
        r = sim.run(offered_load=0.4, n_requests=20_000)
        assert sim.n_link_dropped_req > 0
        lost[pol] = ref.n_completed - r.n_completed
        dropped[pol] = sim.n_link_dropped_req
    assert lost["hedge"] < 0.2 * lost["baseline"]
    # stale-state contract: each single-copy drop is a lost request
    assert lost["netclone"] >= 0.9 * dropped["netclone"]
    assert lost["baseline"] >= 0.9 * dropped["baseline"]


# --------------------------------------------------- two-engine agreement --
def test_chaos_partition_library_scenario_cross_validates():
    sc = load_any("chaos_partition")
    assert sc.link_failure == LinkFailure(start_tick=20_000,
                                          duration=12_000, servers=(2, 3))
    chk = cross_check_scenario(sc, n_ticks=40_000)
    assert chk.ok, chk.describe()


# ------------------------------------------- per-rack tails + wipe counters --
def test_straggler_window_moves_per_rack_p99():
    sc = _sc(policy="baseline", racks=2, servers=4, workers=8,
             n_ticks=12_000, straggler_rack_mult=4.0)
    r = sc.run_fleetsim()
    r_flat = replace(sc, straggler_rack_mult=1.0).run_fleetsim()
    # rack_skew slows the *last* rack; its tail must visibly leave the
    # no-skew tail while the healthy rack stays put
    assert r.rack_p99_us[-1] > 1.5 * r_flat.rack_p99_us[-1]
    assert r.rack_p99_us[0] < 1.5 * r_flat.rack_p99_us[0]


def test_switch_wipe_window_changes_per_rack_p99():
    sc = _sc(policy="baseline", racks=2, servers=4, workers=8,
             n_ticks=12_000, load=0.65)
    r_ok = sc.run_fleetsim()
    r = replace(sc, fail_window_ticks=(4_000, 6_000)).run_fleetsim()
    assert r.n_dropped_down > 0
    assert tuple(r.rack_p99_us) != tuple(r_ok.rack_p99_us)


def test_single_rack_wipe_counters_reconcile_with_trace():
    rng = np.random.default_rng(5)
    counts = tuple(int(c) for c in rng.poisson(1.0, 64))
    sc = _sc(n_ticks=4_000, arrival=TraceArrival(counts=counts),
             fail_window_ticks=(1_600, 2_000))
    r = sc.run_fleetsim()
    tiled = np.tile(counts, -(-4_000 // 64))[:4_000]
    # every arrival in the dark window is dropped at the switch — exactly
    assert r.n_dropped_down == tiled[1_600:2_000].sum()
    assert r.n_arrivals == tiled.sum() - r.n_dropped_down
    assert r.n_completed + r.n_overflow <= r.n_arrivals


# ------------------------------------------- trace replay under TickFuse --
def test_trace_replay_exact_under_fused(monkeypatch):
    # seeded property sweep: for arbitrary (valid) traces, the fused
    # core ingests the exact per-tick counts and its Metrics row is
    # identical to the staged core's
    rng = np.random.default_rng(11)
    for _ in range(3):
        counts = tuple(int(c) for c in rng.poisson(rng.uniform(0.5, 2.0),
                                                   int(rng.integers(8, 48))))
        if not any(counts):
            counts = counts + (1,)
        sc = _sc(n_ticks=1_500, seed=int(rng.integers(1 << 16)),
                 arrival=TraceArrival(counts=counts))
        use_core(monkeypatch, "fused")
        r_fused = sc.run_fleetsim()
        use_core(monkeypatch, "staged")
        r_staged = sc.run_fleetsim()
        assert r_fused.row() == r_staged.row()
        tiled = np.tile(counts, -(-1_500 // len(counts)))[:1_500]
        assert r_fused.n_arrivals == tiled.sum()


# ------------------------------------------------------------- fuzz tier --
@pytest.mark.fuzz
def test_fuzz_smoke_deterministic(tmp_path):
    # the PR-matrix smoke: 5 generated scenarios through the contract,
    # twice — same seed, same verdicts, no counterexamples
    r1 = fuzz_mod.fuzz_contract(seed=7, n=5, out_dir=tmp_path / "a")
    r2 = fuzz_mod.fuzz_contract(seed=7, n=5, out_dir=tmp_path / "b")
    assert r1.ok, r1.describe()
    assert r1.describe() == r2.describe()
    assert not list(tmp_path.glob("*/counterexample_*.json"))


_SMALL_CHOICES = {
    "policy": ("baseline", "netclone"),
    "service": ("exponential",),
    "arrival": ("poisson",),
    "racks": (1,),
    "workers": (8,),
    "load": (0.5,),
    "n_ticks": (3_000,),
    "fail_window": (False,),
    "link_failure": (False, True),
}


@pytest.mark.fuzz
def test_broken_engine_yields_shrunk_replayable_counterexample(
        monkeypatch, tmp_path):
    # deliberately break the DES boundary: service times come out 2x too
    # slow, so every DES-comparable case trips the p50 tolerance.  The
    # driver must shrink the failure to the canonical simplest case and
    # persist it as replayable Scenario JSON.
    monkeypatch.setattr(fuzz_mod, "CHOICES", _SMALL_CHOICES)
    monkeypatch.setattr(
        ServiceSpec, "to_process",
        lambda self: ExponentialService(self.params[0] * 2.0))
    report = fuzz_mod.fuzz_contract(seed=1, n=3, out_dir=tmp_path)
    assert not report.ok
    fail = report.failures[0]
    assert any("cross-check" in f for f in fail.fails)
    assert fail.counterexample.exists()
    cx = Scenario.from_file(fail.counterexample)
    # fully shrunk: every knob at its simplest grid value
    assert cx.policy == "baseline"
    assert cx.link_failure is None and cx.fail_window_ticks is None
    # still failing while the mutation is live...
    assert fuzz_mod.check_case(cx)
    # ...and replayable + passing once the engine is fixed
    monkeypatch.undo()
    assert fuzz_mod.check_case(cx) == []
