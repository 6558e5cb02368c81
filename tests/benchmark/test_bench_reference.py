"""The chip benchmark's plain reference: it draws and answers exactly as
the repository's discrete-event simulator (of which it is a copy), and each
of its controls breaks the one guarantee it names."""

import numpy as np
import pytest

from reference import des  # noqa: E402  (perfbench is on the path)

POLICIES = ["baseline", "c-clone", "netclone", "racksched",
            "netclone+racksched", "laedge", "hedge"]


@pytest.mark.parametrize("load", [0.3, 0.9])
@pytest.mark.parametrize("policy", POLICIES)
def test_one_tor_equals_the_repository_simulator(policy, load):
    from repro.core.simulator import Simulator
    from repro.core.workloads import ExponentialService

    a = Simulator(policy, ExponentialService(25.0), seed=11).run(
        offered_load=load, n_requests=1500)
    b = des.Simulator(policy, des.Service("exponential", (25.0,)),
                      seed=11).run(load, 1500)
    assert np.array_equal(a.latencies_us, b.latencies_us)
    assert (a.n_cloned, a.n_filtered, a.n_clone_drops,
            a.n_redundant_at_client) == (b.n_cloned, b.n_filtered,
                                         b.n_clone_drops,
                                         b.n_redundant_at_client)
    assert a.throughput_mrps == b.throughput_mrps


def _testbed(policy, control=None, load=0.2, seed=3, n=4000):
    return des.Simulator(policy, des.Service("exponential", (25.0,)),
                         n_filter_slots=1024, control=control,
                         seed=seed).run(load, n)


def test_filter_off_lets_redundant_copies_reach_clients():
    on, off = _testbed("netclone"), _testbed("netclone", "filter_off")
    assert off.n_filtered == 0
    assert off.n_redundant_at_client > 10 * max(on.n_redundant_at_client, 1)


@pytest.mark.parametrize("policy", ["netclone", "netclone+racksched"])
def test_clone_unchecked_clones_every_request(policy):
    sound = _testbed(policy, load=0.6)
    broken = _testbed(policy, "clone_unchecked", load=0.6)
    assert sound.n_cloned < 0.9 * sound.n_requests
    assert broken.n_cloned == broken.n_requests


def test_shared_draw_takes_the_gain_of_cloning_away():
    sound = _testbed("c-clone", load=0.1)
    broken = _testbed("c-clone", "shared_draw", load=0.1)
    base = _testbed("baseline", load=0.1)
    p50 = (lambda r: float(np.median(r.latencies_us)))
    assert p50(broken) > 1.3 * p50(sound)
    assert p50(broken) == pytest.approx(p50(base), rel=0.15)


def test_unknown_control_is_refused():
    with pytest.raises(ValueError):
        des.Simulator("netclone", des.Service("exponential", (25.0,)),
                      control="no_such")


def test_horizon_window_counts_what_reaches_a_client_inside_it():
    sim = des.Simulator("baseline", des.Service("exponential", (25.0,)),
                        seed=5)
    rate = des.load_to_rate(0.3, sim.service, 6, 15)
    r = sim.run(0.3, round(rate * 3000.0), horizon_us=3000.0)
    # the window holds 90% of the horizon, less what is still in flight
    assert 0.8 * 0.9 * r.n_requests < r.latencies_us.size <= r.n_requests
    assert r.throughput_mrps / r.offered_rate_mrps == pytest.approx(1.0,
                                                                   abs=0.1)
