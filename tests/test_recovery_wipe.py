"""The §3.6 recovery wipe is compiled in only where a run can recover.

The engine entry reads ``params.fail_until_tick`` and compiles the wipe's
selects into the tick only when some run's switch recovers on a tick of
the horizon (``engine.needs_recovery_wipe``).  Elsewhere the selects were
identities, so every result stays bit-identical to the program that keeps
them:

* a ``run_scenarios`` batch mixing a run that recovers with one that does
  not gives each run the answer it gets alone;
* a window that recovers at ``n_ticks`` (never reached) compiles no wipe,
  and its answer is the one of the program that keeps it;
* params the entry cannot read (a caller's outer ``jit``) keep the wipe;
* ``SweepResult.recovery_wipe`` says which program ran, on the unsharded
  and the sharded sweep.
"""

import jax
import numpy as np
import pytest
from _cores import use_core

from repro.core.workloads import ExponentialService, load_to_rate
from repro.fleetsim import (
    POLICY_IDS,
    FleetConfig,
    ServiceSpec,
    make_params,
    simulate,
    sweep_grid,
)
from repro.fleetsim import engine
from repro.scenarios import registry
from repro.scenarios.spec import Scenario, run_scenarios

SVC = ExponentialService(25.0)
N_TICKS = 900
#: dark from tick 300, recovered (and wiped) at tick 450
WINDOW = (300, 450)
SMALL = dict(n_servers=4, n_workers=8, queue_cap=64, max_arrivals=10,
             n_ticks=N_TICKS)


def _cfg(policy):
    return FleetConfig(service=ServiceSpec.exponential(25.0),
                       **SMALL).with_policy_stages([policy])


def _params(cfg, policy, window, seed=3):
    rate = load_to_rate(0.6, SVC, cfg.n_servers_total, cfg.n_workers)
    return make_params(cfg, POLICY_IDS[policy], rate, seed,
                       fail_window=window)


def _forced(cfg, params, backend, wipe):
    """The entry's program for one run with the wipe forced in or out."""
    return engine._entry(backend, False, False)(
        cfg, registry.version(), wipe, params)


def _equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _scenario(window, seed):
    return Scenario(name=f"wipe-{seed}", policy="netclone", load=0.6,
                    seed=seed, racks=1, servers=4, workers=8,
                    n_ticks=N_TICKS, service=ServiceSpec.exponential(25.0),
                    fail_window_ticks=window)


@pytest.mark.parametrize("backend", ["staged", "fused"])
def test_mixed_batch_matches_each_scenario_alone(backend, monkeypatch):
    """One program serves both scenarios and keeps the wipe (the run
    without a window comes first, so the program is not lowered on its
    params); each row is the answer of that scenario run alone, the one
    without a window on the program without the wipe."""
    use_core(monkeypatch, backend)
    plain, windowed = _scenario(None, 1), _scenario(WINDOW, 2)
    both = run_scenarios([plain, windowed])
    alone = [run_scenarios([sc]) for sc in (plain, windowed)]
    assert both.recovery_wipe is True
    assert [a.recovery_wipe for a in alone] == [False, True]
    assert both.results == [a.results[0] for a in alone]
    assert both.results[1].n_dropped_down > 0


@pytest.mark.parametrize("backend", ["staged", "fused"])
def test_recovery_at_the_horizon_compiles_no_wipe(backend, monkeypatch):
    """``tick == n_ticks`` is never reached: a window recovering there, or
    one that neither opens nor recovers inside the horizon, compiles no
    wipe, and answers as the program that keeps it."""
    use_core(monkeypatch, backend)
    cfg = _cfg("netclone")
    never = _params(cfg, "netclone", (N_TICKS, N_TICKS))
    late = _params(cfg, "netclone", (600, N_TICKS))
    assert not engine.needs_recovery_wipe(cfg, _params(cfg, "netclone",
                                                       None))
    assert not engine.needs_recovery_wipe(cfg, never)
    assert not engine.needs_recovery_wipe(cfg, late)
    assert engine.needs_recovery_wipe(
        cfg, _params(cfg, "netclone", (600, N_TICKS - 1)))
    assert _equal(simulate(cfg, never),
                  simulate(cfg, _params(cfg, "netclone", None)))
    got = simulate(cfg, late)
    assert int(got.n_dropped_down) > 0
    assert _equal(got, _forced(cfg, late, backend, True))


@pytest.mark.parametrize("backend, policy", [
    ("staged", "netclone"), ("staged", "hedge"), ("fused", "netclone")])
def test_simulate_under_outer_jit_keeps_the_wipe(backend, policy,
                                                 monkeypatch):
    """Under a caller's ``jit`` the entry sees tracers and keeps the wipe;
    the eager call reads the window and keeps it too.  Both answer as the
    program with the wipe forced in, and the wipe acts in this run: the
    program with it forced out answers otherwise."""
    use_core(monkeypatch, backend)
    cfg = _cfg(policy)
    assert engine.select_core(cfg) == backend
    params = _params(cfg, policy, WINDOW)
    seen = []

    def traced(p):
        seen.append(engine.needs_recovery_wipe(cfg, p))
        return simulate(cfg, p)

    eager = simulate(cfg, params)
    assert _equal(jax.jit(traced)(params), eager)
    assert seen == [True]
    assert _equal(eager, _forced(cfg, params, backend, True))
    assert not _equal(eager, _forced(cfg, params, backend, False))


@pytest.mark.parametrize("window, wipe", [
    (None, False), (WINDOW, True), ((600, N_TICKS), False)],
    ids=["no-window", "recovers", "recovers-at-horizon"])
def test_sweep_result_reports_the_wipe(window, wipe):
    """``SweepResult.recovery_wipe`` reads the program's choice, sharded
    (one device) or not, and both layouts give the same rows."""
    kw = dict(fail_window_ticks=window, **SMALL)
    one = sweep_grid(SVC, ["netclone", "baseline"], [0.6], [0], **kw)
    mesh = sweep_grid(SVC, ["netclone", "baseline"], [0.6], [0], shard=1,
                      **kw)
    assert one.recovery_wipe is wipe and mesh.recovery_wipe is wipe
    assert one.results == mesh.results
