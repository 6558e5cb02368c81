"""FleetScope telemetry: tracing, time-series, export, and the perf guard.

Contracts of the observability layer (`repro.fleetsim.telemetry`):

* telemetry is a **pure observer** — a telemetry-on run's `Metrics` are
  bit-identical to the telemetry-off run (no PRNG draws, no feedback);
* on an unwrapped ring the event counts reconcile exactly with the run
  counters (`EV_CLONE` covers every `n_cloned` increment site: route,
  coordinator dispatch, hedge fire), and the Chrome-trace export's span
  counts match (`#request spans == n_completed`, `#clone spans ==
  n_cloned` — the ISSUE-6 acceptance criterion);
* the windowed series is an exact decomposition: per-window rate
  increments sum to the final counters;
* the DES `SimResult.row()` and `FleetResult.row()` shared keys are
  pinned (names + rounding) so the engines' result tables can't drift;
* `tools/check_perf_trend.py` passes/fails/re-baselines on the
  `config_ticks_per_s` metric.
"""

import csv
import importlib.util
import json
from dataclasses import replace
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.workloads import ExponentialService, load_to_rate
from repro.fleetsim import (
    POLICY_IDS,
    FleetConfig,
    ServiceSpec,
    TelemetrySpec,
    make_params,
    simulate,
    simulate_telemetry,
    sweep_grid,
)
from repro.fleetsim.metrics import bin_mids_us, hist_percentile
from repro.fleetsim.telemetry import SERIES_COUNTERS, decode_run
from repro.fleetsim.telemetry.events import (
    EV_ARRIVAL,
    EV_CLIENT_COMPLETE,
    EV_CLONE,
    EV_COORD_DISPATCH,
    EV_COORD_ENQ,
    EV_COORD_RESP,
    EV_FILTER_DROP,
    EV_HEDGE_ARMED,
    EV_HEDGE_CANCELLED,
    EV_SERVER_FINISH,
)
from repro.fleetsim.telemetry.export import PID_CLONES, PID_REQUESTS
from repro.scenarios.spec import Scenario, load_any

SVC = ExponentialService(25.0)
S, W = 4, 8
CAP = 1 << 17    # ring depth that never wraps at this scale


def small_cfg(**kw):
    base = dict(n_servers=S, n_workers=W, queue_cap=256, max_arrivals=8,
                n_ticks=3000, service=ServiceSpec.exponential(25.0))
    base.update(kw)
    return FleetConfig(**base)


def run_tel(policy, load=0.5, seed=0, **cfg_kw):
    cfg_kw.setdefault("telemetry", True)
    cfg_kw.setdefault("trace_cap", CAP)
    cfg_kw.setdefault("window_ticks", 1000)
    cfg = small_cfg(**cfg_kw).with_policy_stages([policy])
    rate = load_to_rate(load, SVC, cfg.n_servers, cfg.n_workers)
    params = make_params(cfg, POLICY_IDS[policy], rate, seed)
    m, trace, series = jax.block_until_ready(
        simulate_telemetry(cfg, params))
    return cfg, m, trace, series


# ------------------------------------------------------- pure observer ----
@pytest.mark.parametrize("policy", ["netclone", "hedge", "laedge"])
def test_telemetry_is_a_pure_observer(policy):
    """Compiling the trace/series stages IN leaves every Metrics leaf of
    every policy bit-identical: telemetry draws no PRNG and feeds nothing
    back."""
    cfg_off = small_cfg().with_policy_stages([policy])
    rate = load_to_rate(0.5, SVC, cfg_off.n_servers, cfg_off.n_workers)
    m_off = jax.block_until_ready(
        simulate(cfg_off, make_params(cfg_off, POLICY_IDS[policy], rate, 3)))
    cfg_on = replace(cfg_off, telemetry=True, trace_cap=CAP,
                     window_ticks=1000)
    m_on, _, _ = jax.block_until_ready(simulate_telemetry(
        cfg_on, make_params(cfg_on, POLICY_IDS[policy], rate, 3)))
    for field, off, on in zip(m_off._fields, m_off, m_on):
        assert np.array_equal(np.asarray(off), np.asarray(on)), field


def test_telemetry_entry_points_refuse_flag_off():
    cfg = small_cfg()
    params = make_params(cfg, POLICY_IDS["netclone"], 0.5, 0)
    with pytest.raises(ValueError, match="telemetry"):
        simulate_telemetry(cfg, params)


# ------------------------------------------- event/counter reconciliation --
def test_event_counts_reconcile_with_run_counters():
    cfg, m, trace, series = run_tel("netclone", load=0.6)
    tel = decode_run(cfg, trace, series)
    ev = tel.events
    assert ev.n_lost == 0
    want = {EV_ARRIVAL: m.n_arrivals, EV_CLONE: m.n_cloned,
            EV_SERVER_FINISH: m.n_resp, EV_FILTER_DROP: m.n_filtered,
            EV_CLIENT_COMPLETE: m.n_completed}
    for kind, counter in want.items():
        assert len(ev.select(kind)) == int(counter), kind
    assert int(m.n_cloned) > 0 and int(m.n_filtered) > 0  # non-vacuous


@pytest.mark.parametrize("policy,load", [("laedge", 0.1), ("laedge", 0.5),
                                         ("hedge", 0.6)])
def test_stage_event_counts_reconcile_with_run_counters(policy, load):
    """The coordinator's and the hedge timer's events reconcile with their
    counters: requests accepted, sent and waiting, the CPU's passes
    (received + duplicates + waiting requests sent + responses), and
    hedges armed, fired and cancelled."""
    cfg, m, trace, series = run_tel(policy, load=load)
    ev = decode_run(cfg, trace, series).events
    assert ev.n_lost == 0
    n = {k: len(ev.select(k)) for k in (
        EV_CLONE, EV_COORD_ENQ, EV_COORD_DISPATCH, EV_COORD_RESP,
        EV_HEDGE_ARMED, EV_HEDGE_CANCELLED, EV_FILTER_DROP)}
    assert n[EV_CLONE] == int(m.n_cloned) > 0
    assert n[EV_FILTER_DROP] == int(m.n_filtered)
    if policy == "laedge":
        queued, pending = int(m.n_coord_queued), int(m.n_coord_pending)
        assert n[EV_COORD_ENQ] == queued
        sent_waiting = n[EV_COORD_DISPATCH] - (queued - pending)
        assert 0 <= sent_waiting <= pending
        assert int(m.n_coord_packets) == (
            queued + int(m.n_coord_overflow) + int(m.n_cloned)
            + sent_waiting + n[EV_COORD_RESP])
        waits = ev.arg[ev.kind == EV_COORD_RESP]
        assert (waits >= 0).all()
        assert int(m.n_coord_resp_waited) >= (waits > 0).sum() > 0
    else:
        assert n[EV_HEDGE_ARMED] == int(m.n_hedges_armed)
        assert n[EV_HEDGE_CANCELLED] == int(m.n_hedges_cancelled)
        assert n[EV_COORD_RESP] == n[EV_COORD_ENQ] == 0


@pytest.mark.parametrize("policy", ["laedge", "hedge"])
def test_stage_series_decompose_counters_exactly(policy):
    cfg, m, trace, series = run_tel(policy, load=0.3, window_ticks=500)
    ts = decode_run(cfg, trace, series).series
    for f in SERIES_COUNTERS:
        assert int(ts.rates[f].sum()) == int(getattr(m, f)), f
    moved = ("n_coord_packets", "n_coord_pending") if policy == "laedge" \
        else ("n_hedges_armed", "n_hedges_cancelled")
    assert all(int(ts.rates[f].sum()) > 0 for f in moved)


def test_ring_wrap_flight_recorder():
    """A too-small ring keeps the *latest* cap records in chronological
    order and reports the overwritten remainder as lost."""
    cfg, m, trace, series = run_tel("netclone", load=0.6, trace_cap=256)
    ev = decode_run(cfg, trace, series).events
    assert ev.n_lost > 0
    assert len(ev) == 256
    assert ev.n_emitted == ev.n_lost + 256
    assert np.all(np.diff(ev.tick) >= 0)


# --------------------------------------------------------- windowed series --
def test_series_rates_decompose_counters_exactly():
    cfg, m, trace, series = run_tel("netclone", load=0.6,
                                    window_ticks=500)
    ts = decode_run(cfg, trace, series).series
    assert ts.n_windows == cfg.n_ticks // 500
    for f in SERIES_COUNTERS:
        assert int(ts.rates[f].sum()) == int(getattr(m, f)), f
    assert int(ts.completed_win.sum()) == int(m.n_completed_win)
    assert int(ts.hist.sum()) == int(m.n_completed_win)
    assert np.all(ts.mean_queue_depth >= 0)
    assert np.all(ts.max_queue_depth >= 0)
    rows = ts.rows()
    assert len(rows) == ts.n_windows and rows[0]["window"] == 0


# ------------------------------------------------ acceptance: chrome trace --
def test_trace_burst_chrome_trace_matches_counters():
    """ISSUE-6 acceptance: a telemetry-on ``trace_burst`` run exports a
    Chrome trace whose request spans equal ``n_completed`` and clone spans
    equal ``n_cloned`` — and the document survives a JSON round-trip."""
    sc = load_any("trace_burst")
    result, tel = sc.run_traced(n_ticks=3000)
    assert tel.events.n_lost == 0
    doc = json.loads(json.dumps(tel.chrome_trace(name=sc.name)))
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    n_req = sum(1 for e in spans if e["pid"] == PID_REQUESTS)
    n_clo = sum(1 for e in spans if e["pid"] == PID_CLONES)
    assert n_req == result.n_completed > 0
    assert n_clo == result.n_cloned > 0


# ----------------------------------------------------- spec + scenario JSON --
def test_telemetry_spec_json_round_trip_and_strictness():
    spec = TelemetrySpec(trace_cap=4096, window_ticks=250)
    assert TelemetrySpec.from_json(spec.to_json()) == spec
    with pytest.raises(ValueError, match="unknown telemetry keys"):
        TelemetrySpec.from_json({"enabled": True, "trace_capp": 1})
    with pytest.raises(ValueError):
        TelemetrySpec(trace_cap=-1)
    # a disabled spec keeps the exact flag-off config (same jit cache entry)
    cfg = small_cfg()
    assert TelemetrySpec(enabled=False).apply(cfg) is cfg
    on = TelemetrySpec(trace_cap=4096).apply(cfg)
    assert on.telemetry and on.trace_cap == 4096
    # window is clamped to the run length
    assert TelemetrySpec(window_ticks=10 ** 9).apply(cfg).window_ticks \
        == cfg.n_ticks

    sc = Scenario(name="traced", policy="netclone", servers=S, workers=W,
                  n_ticks=2000, telemetry=spec)
    assert Scenario.from_json(sc.to_json()) == sc
    assert sc.to_json()["telemetry"] == spec.to_json()
    assert sc.fleet_config().telemetry
    assert Scenario.from_json(Scenario(name="plain").to_json()).telemetry \
        is None


# ------------------------------------------------------------- CLI export --
def test_cli_trace_out_writes_bundle(tmp_path):
    from repro.scenarios.__main__ import main

    out = tmp_path / "rows.json"
    assert main(["trace_burst", "--ticks", "2000",
                 "--trace-out", str(tmp_path / "tr"),
                 "--out", str(out)]) == 0
    bundle = tmp_path / "tr" / "trace_burst"
    doc = json.loads((bundle / "trace.json").read_text())
    assert doc["traceEvents"] and doc["metadata"]["tool"] == "fleetscope"
    with (bundle / "events.csv").open() as fh:
        rows = list(csv.DictReader(fh))
    assert rows and {"tick", "event", "rid"} <= set(rows[0])
    with (bundle / "series.csv").open() as fh:
        assert list(csv.DictReader(fh))
    summary = json.loads((bundle / "summary.json").read_text())
    assert summary["result"]["engine"] == "fleetsim"
    assert json.loads(out.read_text())["rows"]


# --------------------------------------------------------------- sweeps ----
def test_sweep_grid_decodes_telemetry_per_row():
    sw = sweep_grid(SVC, ["baseline", "netclone"], [0.3, 0.6], [0],
                    n_servers=S, n_workers=W, n_ticks=1500, queue_cap=48,
                    telemetry=True, trace_cap=CAP, window_ticks=500)
    assert sw.telemetry is not None and len(sw.telemetry) == sw.n_configs
    for r, tel in zip(sw.results, sw.telemetry):
        assert len(tel.events.select(EV_CLIENT_COMPLETE)) == r.n_completed
        assert len(tel.events.select(EV_CLONE)) == r.n_cloned
    # profiling hooks ride on every sweep (backend-permitting)
    assert sw.cost_flops is None or sw.cost_flops > 0
    assert sw.cost_bytes is None or sw.cost_bytes > 0


def test_sweep_grid_rejects_sharded_telemetry():
    with pytest.raises(ValueError, match="cannot shard"):
        sweep_grid(SVC, ["baseline"], [0.4], [0], n_servers=S, n_workers=W,
                   n_ticks=1000, telemetry=True, shard=2)


# ------------------------------------------------------- row key parity ----
# the frozen shared vocabulary of the two engines' result rows: identical
# names, units, and rounding (throughput 4 d.p., latencies 1 d.p., empty_q
# 3 d.p.) — extend deliberately, in both row() methods at once
SHARED_ROW_KEYS = frozenset({
    "policy", "load", "throughput_mrps", "p50_us", "p99_us", "p999_us",
    "mean_us", "cloned", "filtered", "clone_drops", "redundant", "empty_q",
})


def test_result_row_key_parity_with_des():
    sc = Scenario(name="parity", policy="netclone", servers=S, workers=W,
                  n_ticks=1500, load=0.5)
    fs = sc.run_fleetsim().row()
    des = sc.run_des(n_requests=800).row()
    assert set(fs) & set(des) == SHARED_ROW_KEYS
    for k in SHARED_ROW_KEYS:
        assert type(fs[k]) is type(des[k]), k


# ------------------------------------------------ hist_percentile edges ----
def test_hist_percentile_edge_cases():
    mids = bin_mids_us(small_cfg())[:5]
    assert np.isnan(hist_percentile(np.zeros(5, np.int64), mids, 50.0))
    # all mass in one bin: every quantile answers that bin
    one = np.array([0, 0, 7, 0, 0])
    for q in (0.0, 50.0, 100.0):
        assert hist_percentile(one, mids, q) == pytest.approx(mids[2])
    # q=0 → first occupied bin, q=100 → last occupied bin
    two = np.array([3, 0, 0, 0, 1])
    assert hist_percentile(two, mids, 0.0) == pytest.approx(mids[0])
    assert hist_percentile(two, mids, 100.0) == pytest.approx(mids[4])
    assert hist_percentile(two, mids, 50.0) == pytest.approx(mids[0])


# ------------------------------------------------------- perf-trend guard --
def _perf_trend():
    path = Path(__file__).parent.parent / "tools" / "check_perf_trend.py"
    spec = importlib.util.spec_from_file_location("check_perf_trend", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _artifact(path, wall, n_configs=10, n_ticks=1000):
    path.write_text(json.dumps({"n_configs": n_configs, "n_ticks": n_ticks,
                                "wall_clock_s": wall}))
    return path


def test_check_perf_trend_pass_fail_and_rebaseline(tmp_path, capsys):
    mod = _perf_trend()
    base = _artifact(tmp_path / "base.json", wall=1.0)       # 10k ct/s
    ok = _artifact(tmp_path / "ok.json", wall=1.2)           # -17%: inside
    slow = _artifact(tmp_path / "slow.json", wall=2.0)       # -50%: beyond
    argv = ["--baseline", str(base)]
    assert mod.main(["--fresh", str(ok), *argv]) == 0
    assert "PASS" in capsys.readouterr().out
    assert mod.main(["--fresh", str(slow), *argv]) == 1
    assert "FAIL" in capsys.readouterr().out
    # a wider margin admits the same artifact
    assert mod.main(["--fresh", str(slow), "--max-regression", "0.6",
                     *argv]) == 0
    # deliberate re-baseline: the reference becomes the fresh artifact
    assert mod.main(["--fresh", str(slow), "--update-baseline", *argv]) == 0
    assert mod.main(["--fresh", str(slow), *argv]) == 0
    # unusable artifacts are a distinct failure mode
    bad = _artifact(tmp_path / "bad.json", wall=0.0)
    assert mod.main(["--fresh", str(bad), *argv]) == 2
    with pytest.raises(SystemExit, match="does not exist"):
        mod.main(["--fresh", str(tmp_path / "missing.json"), *argv])
    assert mod.config_ticks_per_s(
        {"n_configs": 10, "n_ticks": 1000, "wall_clock_s": 1.0}) \
        == pytest.approx(10_000.0)


def test_check_perf_trend_trajectory_keyed_per_backend(tmp_path, capsys):
    """A trajectory baseline only judges same-(backend, n_devices) rows: a
    fused artifact never fails against the staged row, a missing row passes
    with a notice, and --update-baseline upserts without touching the other
    backends' rows."""
    mod = _perf_trend()
    traj = tmp_path / "traj.json"
    traj.write_text(json.dumps({"baselines": [
        {"backend": "staged", "n_devices": 1, "n_configs": 10,
         "n_ticks": 1000, "wall_clock_s": 1.0},      # 10k ct/s
    ]}))
    argv = ["--baseline", str(traj)]

    # fused fresh, no fused row yet: PASS (no baseline), even though it is
    # far slower than the staged row
    fused_slow = tmp_path / "fused.json"
    fused_slow.write_text(json.dumps({"backend": "fused", "n_configs": 10,
                                      "n_ticks": 1000, "wall_clock_s": 8.0}))
    assert mod.main(["--fresh", str(fused_slow), *argv]) == 0
    assert "no baseline" in capsys.readouterr().out

    # upsert the fused row; the staged row survives verbatim
    assert mod.main(["--fresh", str(fused_slow), "--update-baseline",
                     *argv]) == 0
    doc = json.loads(traj.read_text())
    assert [mod.artifact_key(r) for r in doc["baselines"]] == \
        [("fused", 1), ("staged", 1)]
    assert doc["baselines"][1]["wall_clock_s"] == 1.0

    # now a same-key regression fails...
    fused_slower = tmp_path / "fused2.json"
    fused_slower.write_text(json.dumps({"backend": "fused", "n_configs": 10,
                                        "n_ticks": 1000,
                                        "wall_clock_s": 20.0}))
    assert mod.main(["--fresh", str(fused_slower), *argv]) == 1
    # ...while the staged row still judges staged runs independently
    staged_ok = _artifact(tmp_path / "staged_ok.json", wall=1.1)
    assert mod.main(["--fresh", str(staged_ok), *argv]) == 0
