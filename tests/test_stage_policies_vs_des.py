"""The coordinator and hedge-timer stages against the discrete-event
simulator, at the paper testbed's shape (6 servers x 15 workers, 2^17-slot
filter tables as the DES keeps them).

* ``validate.cross_validate_spec`` passes LÆDGE and delayed hedging at
  loads 0.1, 0.35 and 0.65 on three seeds: LÆDGE's coordinator CPU
  collapses the rack where the DES's does, and hedging fires, cancels and
  filters its duplicates as the DES does; a coordinator whose CPU costs
  nothing, or a hedge timer that never cancels, fails it;
* in a run that drains, every fired hedge is accounted for exactly as the
  DES accounts for it (``test_hedge_counts_balance``): filtered at the
  switch, dropped by the CLO=2 rule, or redundant at its client;
* the coordinator's CPU charges one pass per request received, duplicate
  sent, waiting request sent and response, as the DES's does.
"""

import jax
import numpy as np
import pytest

from repro.core.simulator import Simulator
from repro.core.switch import FILTER_SLOTS
from repro.core.workloads import ExponentialService, load_to_rate
from repro.fleetsim import (POLICY_IDS, FleetConfig, make_params, simulate,
                            stages)
from repro.fleetsim.validate import cross_validate_spec
from repro.scenarios import Scenario, SweepSpec

SVC = ExponentialService(25.0)
S, W = 6, 15
LOADS = (0.1, 0.35, 0.65)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("policy", ["laedge", "hedge"])
def test_stage_policy_agrees_with_des_at_testbed_shape(policy, seed):
    spec = SweepSpec(base=Scenario(name="testbed", servers=S, workers=W,
                                   n_ticks=4000, seed=seed),
                     policies=(policy,), loads=LOADS, seeds=(seed,))
    checks = cross_validate_spec(spec, n_requests=20_000, n_ticks=4000)
    failed = [c.describe() for c in checks if not c.ok]
    assert not failed, "cross-validation failures:\n" + "\n".join(failed)
    by = {c.load: c for c in checks}
    if policy == "laedge":
        # every load is past the coordinator CPU: both engines collapse
        assert all(c.des_goodput < 0.9 and c.fleet_goodput < 0.9
                   for c in checks)
    else:
        for c in checks:
            assert c.fleet_clone_frac == pytest.approx(c.des_clone_frac,
                                                       abs=0.03)
        assert by[0.35].fleet_filter_frac > 0.95


def _free_coordinator_cpu(monkeypatch):
    real = Scenario.fleet_config
    monkeypatch.setattr(Scenario, "fleet_config",
                        lambda self, **kw: real(self, **kw, coord_cpu_us=0.0))


def _hedge_never_cancels(monkeypatch):
    monkeypatch.setattr(stages, "stage_hedge_cancel",
                        lambda cfg, params, state, arr, resp: state)


@pytest.mark.parametrize("policy,plant", [
    ("laedge", _free_coordinator_cpu),
    ("hedge", _hedge_never_cancels),
], ids=["free_coordinator_cpu", "hedge_never_cancels"])
def test_planted_stage_fault_fails_the_cross_validation(policy, plant,
                                                        monkeypatch):
    """The check above can see each stage's fault: a coordinator that
    delivers where the DES's CPU collapses, a hedge that fires after its
    original answered."""
    plant(monkeypatch)
    spec = SweepSpec(base=Scenario(name="testbed", servers=S, workers=W,
                                   n_ticks=4000, seed=0),
                     policies=(policy,), loads=(0.1,), seeds=(0,))
    jax.clear_caches()
    try:
        checks = cross_validate_spec(spec, n_requests=20_000, n_ticks=4000)
    finally:
        jax.clear_caches()
    assert not checks[0].ok, checks[0].describe()


def _drained(policy, load, seed, quiet_from=8000):
    """A 12,000-tick run whose arrivals stop at ``quiet_from``, early
    enough that every copy in flight completes inside it."""
    cfg = FleetConfig(n_servers=S, n_workers=W, n_ticks=12_000,
                      max_arrivals=16, n_filter_slots=FILTER_SLOTS,
                      arrival="trace").with_policy_stages([policy])
    rate = load_to_rate(load, SVC, S, W)
    counts = np.random.default_rng(seed).poisson(rate, cfg.n_ticks)
    counts[quiet_from:] = 0
    params = make_params(cfg, POLICY_IDS[policy], rate, seed,
                         arrival_counts=counts)
    return cfg, jax.block_until_ready(simulate(cfg, params)), counts


@pytest.mark.parametrize("load", [0.2, 0.65])
def test_fired_hedges_balance_as_in_the_des(load):
    _, m, counts = _drained("hedge", load, seed=7)
    fired = int(m.n_cloned)          # a hedge's only copies are fired ones
    assert fired > 0
    assert int(m.n_hedges_armed) == int(m.n_arrivals) == counts.sum()
    # every armed timer fired or was cancelled: none is left in the wheel
    assert int(m.n_hedges_cancelled) + fired == int(m.n_hedges_armed)
    assert int(m.n_wheel_dropped) == 0 and int(m.n_overflow) == 0
    # the DES's accounting: each duplicate is filtered, dropped at its
    # server, or redundant at its client
    assert int(m.n_filtered) + int(m.n_clone_drops) + int(m.n_redundant) \
        == fired
    des = Simulator("hedge", SVC, n_servers=S, n_workers=W, seed=7).run(
        offered_load=load, n_requests=int(counts.sum()))
    assert des.n_filtered + des.n_clone_drops + des.n_redundant_at_client \
        == des.n_cloned
    assert fired / int(m.n_arrivals) == pytest.approx(
        des.n_cloned / des.n_requests, abs=0.02)


def test_coordinator_charges_the_des_passes():
    """One pass per request received, duplicate sent, waiting request sent
    and response: in a drained run those are arrivals + 2 x clones +
    waiting requests + arrivals (every copy's response)."""
    cfg, m, counts = _drained("laedge", 0.1, seed=3, quiet_from=4000)
    arrivals, clones = int(m.n_arrivals), int(m.n_cloned)
    assert int(m.n_coord_overflow) == 0
    assert int(m.n_coord_queued) == arrivals == counts.sum()
    assert int(m.n_completed) == arrivals
    assert int(m.n_filtered) == clones           # every pair absorbed
    assert int(m.n_coord_packets) == (arrivals + clones
                                      + int(m.n_coord_pending)
                                      + arrivals + clones)
    # the CPU is the bottleneck: it is busy longer than the arrivals last,
    # most responses wait for it, and requests wait for a server
    busy = int(m.n_coord_packets) * cfg.coord_cpu_us
    assert busy > 4000
    assert int(m.n_coord_resp_waited) > 0.5 * (arrivals + clones)
    assert int(m.n_coord_pending) > 0
