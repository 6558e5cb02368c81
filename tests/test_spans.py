"""Stage scopes in the compiled programs, host phase spans and compile
counters of a sweep call (``repro.fleetsim.spans``)."""

import re
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest
from _cores import use_core

from repro.core.workloads import ExponentialService, load_to_rate
from repro.fleetsim import (
    POLICY_IDS,
    FleetConfig,
    ServiceSpec,
    make_params,
)
from repro.fleetsim.engine import lower
from repro.fleetsim.fused import DEFAULT_TICKS_PER_CHUNK
from repro.fleetsim.spans import (
    COUNTERS,
    compile_counts,
    compile_events,
    phase,
)
from repro.fleetsim.shard import ShardSpec
from repro.fleetsim.sweep import sweep_grid
from repro.scenarios import Scenario, TraceArrival, run_scenarios

SVC = ExponentialService(25.0)
TINY = dict(n_servers=4, n_workers=8, queue_cap=64, max_arrivals=10,
            n_ticks=64)
DEFAULT_SCOPES = {"tick.arrival", "tick.route", "tick.link", "tick.server",
                  "tick.filter", "tick.client", "fleetsim.init",
                  "fleetsim.draw"}
OPTIONAL_SCOPES = {"tick.coordinator", "tick.hedge_timer"}
PHASES = {"params", "lower", "compile", "device", "fetch", "summarize"}


def compiled_scopes(cfg, policies, fail_window=None):
    """Every ``tick.*``/``fleetsim.*`` scope named in the ``op_name``
    metadata of the compiled batch program of ``policies``."""
    rate = load_to_rate(0.5, SVC, cfg.n_servers_total, cfg.n_workers)
    rows = [make_params(cfg, POLICY_IDS[p], rate, 0, fail_window=fail_window)
            for p in policies]
    params = jax.tree.map(lambda *a: jnp.stack(a), *rows)
    text = lower(cfg, params).compile().as_text()
    return {m.group(0) for op in re.findall(r'op_name="([^"]*)"', text)
            for m in re.finditer(r"(tick|fleetsim)\.[a-z_]+", op)}


def tiny_cfg(**kw):
    return FleetConfig(service=ServiceSpec.exponential(25.0), **TINY, **kw)


@pytest.mark.parametrize("backend", ["staged", "fused"])
def test_default_programs_carry_every_default_stage_scope(backend,
                                                          monkeypatch):
    use_core(monkeypatch, backend)
    # four fused chunks, so the carry is packed between chunks
    cfg = replace(tiny_cfg(), n_ticks=4 * DEFAULT_TICKS_PER_CHUNK)
    scopes = compiled_scopes(cfg, ["baseline", "netclone"])
    assert DEFAULT_SCOPES <= scopes
    # stages compiled out leave no op behind to carry their scope
    assert not scopes & OPTIONAL_SCOPES
    assert ("fleetsim.pack" in scopes) == (backend == "fused")
    assert "tick.telemetry" not in scopes


def test_optional_stage_scopes_appear_when_compiled_in():
    policies = ["netclone", "laedge", "hedge"]
    cfg = replace(tiny_cfg(), telemetry=True,
                  window_ticks=16).with_policy_stages(policies)
    assert cfg.coordinator and cfg.hedge_timer
    scopes = compiled_scopes(cfg, policies)
    assert DEFAULT_SCOPES | OPTIONAL_SCOPES | {"tick.telemetry"} <= scopes


@pytest.mark.parametrize("backend", ["staged", "fused"])
def test_wipe_scope_only_where_a_run_recovers(backend, monkeypatch):
    """The §3.6 wipe runs under ``tick.arrival/tick.wipe`` in a program
    whose window recovers inside the horizon, and a window-free program
    holds no op of it."""
    use_core(monkeypatch, backend)
    plain = compiled_scopes(tiny_cfg(), ["netclone"])
    windowed = compiled_scopes(tiny_cfg(), ["netclone"],
                               fail_window=(16, 32))
    assert "tick.wipe" not in plain
    assert DEFAULT_SCOPES | {"tick.wipe"} <= windowed


def _sweep_unsharded():
    return sweep_grid(SVC, ["baseline", "netclone"], [0.5], [0], **TINY)


def _sweep_sharded():
    return sweep_grid(SVC, ["baseline", "netclone"], [0.5], [0],
                      shard=ShardSpec(devices=1), **TINY)


def _trace_scenarios():
    scs = [Scenario(name=f"tr{i}", policy=p, servers=4, workers=8,
                    n_ticks=64, arrival=TraceArrival(counts=(1, 0, 2, 1)))
           for i, p in enumerate(["baseline", "netclone"])]
    return run_scenarios(scs)


@pytest.mark.parametrize("call", [_sweep_unsharded, _sweep_sharded,
                                  _trace_scenarios],
                         ids=["unsharded", "sharded", "run_scenarios"])
def test_sweep_phases_and_their_sums(call):
    sw = call()
    assert set(sw.phases) == PHASES
    assert all(v >= 0.0 for v in sw.phases.values())
    assert sw.compile_s == sw.phases["lower"] + sw.phases["compile"]
    assert sw.wall_clock_s == sw.phases["device"]
    assert set(sw.compile_events) == set(COUNTERS)


def test_repeated_call_reuses_the_program(monkeypatch):
    use_core(monkeypatch, "fused")
    kw = dict(policies=["baseline"], loads=[0.3], seeds=[0], **TINY)
    kw["n_ticks"] = 48      # a program no other test compiles
    first = sweep_grid(SVC, **kw).compile_events
    again = sweep_grid(SVC, **{**kw, "seeds": [1]}).compile_events
    assert first["backend_compile"] == 1 and first["mlir_lower"] == 1
    # the entry's own jit reports one trace event per lower() even when
    # its trace is cached; a real trace reports every nested jit as well
    assert first["jaxpr_trace"] > 1
    assert again["jaxpr_trace"] <= 1
    assert again["mlir_lower"] == 0 and again["backend_compile"] == 0
    assert again["cache_hits"] == 0 and again["cache_misses"] == 0


def test_phase_adds_up_and_counters_difference():
    phases = {}
    before = compile_counts()
    for _ in range(2):
        with phase(phases, "fetch"):
            pass
    with pytest.raises(RuntimeError):
        with phase(phases, "summarize"):
            raise RuntimeError("a failed phase is still timed")
    assert set(phases) == {"fetch", "summarize"}
    assert all(v >= 0.0 for v in phases.values())
    assert set(compile_events(before)) == set(COUNTERS)


def test_nested_traces_count_their_time_once():
    """A jit traced inside another reports its own trace span; the time
    counter keeps the enclosing span alone, so it stays within the wall
    time of the trace."""
    def nested(i):
        @jax.jit
        def f(x):
            for _ in range(60):
                x = jnp.sin(x) * i
            return x
        return f

    inner = [nested(i) for i in range(8)]

    @jax.jit
    def outer(x):
        for f in inner:
            x = f(x)
        return x

    x = jnp.ones(4)
    before = compile_counts()
    t0 = time.perf_counter()
    outer.trace(x)
    wall = time.perf_counter() - t0
    ev = compile_events(before)
    assert ev["jaxpr_trace"] >= 1 + len(inner)
    assert 0.0 < ev["jaxpr_trace_s"] <= wall
