"""Staged tick pipeline: stage compile-out, timer wheel, coordinator node.

Contracts of the stage refactor (PR 4):

* optional stages are **static**: a flag-off config runs the exact program
  the pre-stage engine built (covered by the goldens in
  ``test_fleetsim_fabric``), and — stronger — compiling the stages *in*
  leaves every non-stage policy bit-identical, because the coordinator and
  wheel draw no shared PRNG traffic and their lanes stay inactive;
* the timer wheel never drops an armed hedge while its slot has room, and
  drops deterministically (latest lanes first) when it is full;
* the coordinator implements LÆDGE's clone-iff-≥2-idle / queue-otherwise
  rule, and its one FCFS CPU collapses the rack where the DES's does.
"""

import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.simulator import Simulator
from repro.core.workloads import ExponentialService, load_to_rate
from repro.fleetsim import (
    POLICY_IDS,
    FleetConfig,
    ServiceSpec,
    make_params,
    simulate,
    summarize,
)
from repro.fleetsim.stages import wheel_arm, wheel_fire
from repro.fleetsim.state import WH, init_hedge_wheel

SVC = ExponentialService(25.0)
S, W = 4, 8
GOLDEN = Path(__file__).parent / "golden" / "fleetsim_single_tor.json"


def small_cfg(**kw):
    base = dict(n_servers=S, n_workers=W, queue_cap=256, max_arrivals=8,
                n_ticks=4000, service=ServiceSpec.exponential(25.0))
    base.update(kw)
    return FleetConfig(**base)


def run(policy, load=0.4, seed=0, cfg=None, **param_kw):
    cfg = (cfg or small_cfg()).with_policy_stages([policy])
    rate = load_to_rate(load, SVC, cfg.n_servers, cfg.n_workers)
    params = make_params(cfg, POLICY_IDS[policy], rate, seed, **param_kw)
    return cfg, jax.block_until_ready(simulate(cfg, params))


def result(policy, load=0.4, seed=0, cfg=None, **param_kw):
    cfg, m = run(policy, load, seed, cfg, **param_kw)
    rate = load_to_rate(load, SVC, cfg.n_servers, cfg.n_workers)
    return summarize(cfg, m, policy=policy, load=load, rate_per_us=rate,
                     seed=seed)


# ----------------------------------------------------- stage compile-out ----
def test_stage_flags_resolve_and_validate():
    cfg = small_cfg(coordinator=True, hedge_timer=True)
    assert cfg.hedge_delay_ticks == 75
    assert cfg.wheel_slots == 76
    assert cfg.wheel_width == cfg.max_arrivals
    # one 1.5 µs CPU pass per copy: at most one copy leaves per 1 µs tick;
    # a CPU that costs nothing sends all a tick can make
    assert cfg.coord_sends_per_tick == 1
    assert replace(cfg, coord_cpu_us=0.0).coord_sends_per_tick \
        == 2 * cfg.max_arrivals + cfg.max_responses
    with pytest.raises(ValueError, match="coord_cpu_us"):
        small_cfg(coordinator=True, coord_cpu_us=-1.0)
    with pytest.raises(ValueError, match="delay horizon"):
        small_cfg(hedge_timer=True, hedge_wheel_slots=10)
    with pytest.raises(ValueError, match="coordinator_cap"):
        small_cfg(coordinator=True, coordinator_cap=0)
    # with_policy_stages only flips what the policy set needs
    assert small_cfg().with_policy_stages(["netclone"]) == small_cfg()
    assert small_cfg().with_policy_stages(["laedge"]).coordinator
    assert small_cfg().with_policy_stages(["hedge"]).hedge_timer
    assert not small_cfg().with_policy_stages(["hedge"]).coordinator


def test_stage_policies_refuse_flagless_configs():
    cfg = small_cfg()
    with pytest.raises(ValueError, match="coordinator stage"):
        make_params(cfg, POLICY_IDS["laedge"], 0.5, 0)
    with pytest.raises(ValueError, match="hedge_timer stage"):
        make_params(cfg, POLICY_IDS["hedge"], 0.5, 0)


def test_enabled_stages_leave_stock_policies_bit_identical():
    """Compiling the coordinator + wheel stages IN changes nothing for
    policies that use neither: their lanes stay inactive and the stages
    draw no shared PRNG traffic.  Checked against the same goldens the
    flag-off engine is checked against — every metric, full histogram."""
    g = json.loads(GOLDEN.read_text())
    cfg = FleetConfig(service=ServiceSpec.exponential(25.0), **g["cfg"])
    cfg = replace(cfg, coordinator=True, hedge_timer=True)
    for c in g["cases"]:
        if "slowdown" in c or "fail_window" in c:
            continue
        rate = load_to_rate(c["load"], SVC, cfg.n_servers, cfg.n_workers)
        params = make_params(cfg, POLICY_IDS[c["policy"]], rate, c["seed"])
        m = jax.block_until_ready(simulate(cfg, params))
        for field, want in c["metrics"].items():
            got = np.asarray(getattr(m, field)).reshape(-1)
            assert np.array_equal(got, np.asarray(want).reshape(-1)), \
                (c["policy"], field)


# ----------------------------------------------------------- timer wheel ----
def _wheel(slots=8, width=4):
    cfg = small_cfg(hedge_timer=True, hedge_wheel_slots=slots,
                    hedge_wheel_width=width, hedge_delay_us=3.0)
    return init_hedge_wheel(cfg)


def _rows(ids):
    rows = np.zeros((len(ids), WH), np.float32)
    rows[:, 0] = ids
    return jnp.asarray(rows)


def test_wheel_fires_exactly_at_due_tick():
    wheel = _wheel()
    delay = 3
    wheel, armed, dropped = wheel_arm(wheel, jnp.int32(0), delay,
                                      jnp.array([True, True]), _rows([7, 9]))
    assert armed.tolist() == [True, True] and not any(dropped.tolist())
    for tick in range(1, 3):
        wheel, due, _ = wheel_fire(wheel, jnp.int32(tick))
        assert int(due.sum()) == 0
    wheel, due, entries = wheel_fire(wheel, jnp.int32(3))
    assert int(due.sum()) == 2
    assert sorted(np.asarray(entries)[np.asarray(due), 0].tolist()) == [7, 9]
    # the slot drained: one full rotation later nothing re-fires
    wheel, due, _ = wheel_fire(wheel, jnp.int32(3 + 8))
    assert int(due.sum()) == 0


@given(st.lists(st.integers(min_value=0, max_value=6), min_size=1,
                max_size=24))
@settings(max_examples=60, deadline=None)
def test_wheel_never_drops_while_free_and_drops_deterministically(arms):
    """Property: arming ``arms[t]`` hedges at tick ``t`` (fixed delay), the
    wheel drops exactly ``max(0, k - width)`` per tick — never a hedge
    while the slot has room — and the dropped lanes are the latest ones;
    every armed entry fires exactly once, ``delay`` ticks later."""
    width, delay, slots = 4, 3, 8
    wheel = _wheel(slots=slots, width=width)
    rid = 1
    fired_ids, armed_ids = [], []
    for tick in range(len(arms) + delay + 1):
        wheel, due, entries = wheel_fire(wheel, jnp.int32(tick))
        ids = np.asarray(entries)[np.asarray(due), 0].astype(int).tolist()
        fired_ids += ids
        k = arms[tick] if tick < len(arms) else 0
        ids = list(range(rid, rid + k))
        rid += k
        mask = jnp.arange(max(k, 1)) < k
        wheel, armed, dropped = wheel_arm(wheel, jnp.int32(tick), delay,
                                          mask, _rows(ids or [0]))
        armed_np = np.asarray(armed)[:k]
        # never drop while the slot has room; beyond it, latest lanes lose
        assert armed_np.tolist() == [i < width for i in range(k)]
        assert int(np.asarray(dropped).sum()) == max(0, k - width)
        armed_ids += [i for i, a in zip(ids, armed_np) if a]
    assert sorted(fired_ids) == sorted(armed_ids)


# ---------------------------------------------------------------- hedging ----
def test_hedge_arms_every_arrival_and_balances():
    cfg, m = run("hedge", load=0.4, seed=3)
    assert int(m.n_hedges_armed) == int(m.n_arrivals)
    assert int(m.n_wheel_dropped) == 0       # width defaults to max_arrivals
    # every armed hedge fires (n_cloned) or is cancelled, modulo the wheel
    # entries still pending at scan end
    pending = int(m.n_hedges_armed) - int(m.n_cloned) \
        - int(m.n_hedges_cancelled)
    assert 0 <= pending <= cfg.wheel_slots * cfg.wheel_width
    # hedging is surgical: far fewer duplicates than arrivals
    assert 0 < int(m.n_cloned) < 0.25 * int(m.n_arrivals)


def test_hedge_pays_delay_floor_but_beats_baseline_tail():
    """The DES contract (test_hedge_vs_netclone_low_load), in the fast
    engine: NetClone's clones race from t=0, hedging pays the delay on
    every masked straggler, and both beat the baseline."""
    cfg = small_cfg(n_ticks=20_000)
    nc = result("netclone", load=0.15, cfg=cfg)
    hg = result("hedge", load=0.15, cfg=cfg)
    base = result("baseline", load=0.15, cfg=cfg)
    assert nc.p99_us < hg.p99_us < base.p99_us


def test_hedge_cancellation_tracks_fast_responses():
    """Most requests finish well inside the 75 µs delay at low load, so
    most armed hedges must be cancelled rather than fired."""
    _, m = run("hedge", load=0.2, cfg=small_cfg(n_ticks=12_000))
    assert int(m.n_hedges_cancelled) > 4 * int(m.n_cloned)


# ------------------------------------------------------------- coordinator --
def test_laedge_queues_everything_and_clones_when_idle():
    cfg, m = run("laedge", load=0.05, cfg=small_cfg(n_ticks=12_000))
    # every admitted arrival goes through the coordinator ring
    assert int(m.n_coord_queued) == int(m.n_arrivals)
    assert int(m.n_coord_overflow) == 0
    # ≥2 idle almost always at 5% load → nearly everything clones, and the
    # slower copy of each pair is absorbed exactly once
    assert int(m.n_cloned) > 0.9 * int(m.n_arrivals)
    assert int(m.n_clone_drops) == 0         # LÆDGE copies are CLO_ORIG
    assert int(m.n_filtered) <= int(m.n_cloned)
    assert int(m.n_filtered) > 0.9 * int(m.n_cloned)


def test_laedge_coordinator_cpu_bottleneck():
    """The paper's §2.2 argument in one assertion: the coordinator CPU
    (not the servers) caps LÆDGE throughput, far below what the same
    cluster serves under switch-based policies.  Every request costs the
    CPU a pass on receipt, so once receipts alone need more than the CPU
    has, its backlog grows all run long, copies and responses wait behind
    it, and the rack delivers almost nothing — as in the DES."""
    cfg = small_cfg(n_ticks=20_000)
    la = result("laedge", load=0.6, cfg=cfg)
    nc = result("netclone", load=0.6, cfg=cfg)
    assert nc.throughput_mrps > 0.9 * nc.offered_rate_mrps
    assert la.offered_rate_mrps * cfg.coord_cpu_us > 1.0
    des = Simulator("laedge", SVC, n_servers=S, n_workers=W, seed=0).run(
        offered_load=0.6, n_requests=13_500)
    assert des.throughput_mrps < 0.1 * des.offered_rate_mrps
    assert la.throughput_mrps < 0.1 * la.offered_rate_mrps
    assert la.throughput_mrps == pytest.approx(des.throughput_mrps, rel=0.3)
    # the backlog is visible: every arrival was accepted or shed at the
    # ring, and the CPU was busy the whole run
    assert la.n_coord_queued + la.n_coord_overflow == la.n_arrivals
    assert la.n_coord_overflow > 0 or la.n_coord_queued > la.n_completed
    assert la.n_coord_packets * cfg.coord_cpu_us > cfg.duration_us


def test_laedge_multirack_runs_and_filters_at_top_tier():
    """The coordinator is fabric-global: a 2-rack LÆDGE run dispatches
    across racks and absorbs every pair at the top-tier filter group."""
    cfg = FleetConfig(n_racks=2, n_servers=4, n_workers=8, queue_cap=64,
                      max_arrivals=10, n_ticks=6000,
                      service=ServiceSpec.exponential(25.0),
                      coordinator=True)
    rate = load_to_rate(0.05, SVC, cfg.n_servers_total, cfg.n_workers)
    params = make_params(cfg, POLICY_IDS["laedge"], rate, 0)
    m = jax.block_until_ready(simulate(cfg, params))
    assert int(m.n_completed) > 0 and int(m.n_cloned) > 0
    # LÆDGE pairs are filtered in the spine's table group
    assert int(m.n_spine_filtered) == int(m.n_filtered) > 0


def test_staged_policies_deterministic_given_seed():
    for policy in ("hedge", "laedge"):
        _, a = run(policy, seed=11)
        _, b = run(policy, seed=11)
        assert jax.tree.all(jax.tree.map(
            lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
            a, b))


@pytest.mark.parametrize("backend", ["scan", "pallas"])
def test_staged_policies_filter_backends_match(backend):
    for policy in ("hedge", "laedge"):
        _, ref = run(policy, load=0.3, seed=7)
        _, alt = run(policy, load=0.3, seed=7,
                     cfg=small_cfg(filter_backend=backend))
        for f in ref._fields:
            assert np.array_equal(np.asarray(getattr(ref, f)),
                                  np.asarray(getattr(alt, f))), (policy, f)
