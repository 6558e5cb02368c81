"""ServeSim: llm service specs, the batch server stage, and its oracle.

Contracts:

* the ``llm`` ServiceSpec kind round-trips through JSON and both engines'
  process forms, and ServiceSpec validation rejects non-positive
  parameters at construction with actionable errors;
* :func:`repro.fleetsim.llmserve.llm_service` derives decode/prefill costs
  from the roofline (memory-bound for dense registry models);
* ``server_model="fcfs"`` is the *exact* program it always was — checked
  against the PR-2 goldens with the flag passed explicitly — and
  ``server_model="batch"`` with ``batch_coupling=0`` and one slot per
  worker is arithmetically identical to the FCFS ring across the policy
  matrix (admit-into-free-slot ≡ dequeue-onto-free-worker when every busy
  slot progresses independently);
* the batch stage exports slot occupancy, and the serve-equivalence tier
  holds it to the real-model DecodeReplica oracle within the documented
  ``SERVE_*`` tolerances.
"""

import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.workloads import LLMBimodalService, load_to_rate
from repro.fleetsim import (
    POLICY_IDS,
    FleetConfig,
    ServiceSpec,
    make_params,
    simulate,
    sweep_grid,
)
from repro.fleetsim.llmserve import decode_step_us, llm_service, prefill_us
from repro.fleetsim.stages import _intrinsic
from repro.scenarios.spec import Scenario

GOLDEN = Path(__file__).parent / "golden" / "fleetsim_single_tor.json"


# ------------------------------------------------------------ service spec --
def test_llm_spec_roundtrips_and_matches_process():
    spec = ServiceSpec.llm(prefill=200.0, decode=10.0, gen_short=8.0,
                           gen_long=64.0, p_long=0.25)
    assert spec.mean == 200.0 + 10.0 * (0.75 * 8 + 0.25 * 64)
    assert ServiceSpec.from_json(spec.to_json()) == spec
    proc = spec.to_process()
    assert isinstance(proc, LLMBimodalService)
    assert ServiceSpec.from_process(proc) == spec
    draws = proc.intrinsic(np.random.default_rng(0), 4000)
    assert {280.0, 840.0} == set(np.unique(draws).tolist())
    assert abs(draws.mean() - spec.mean) < 0.03 * spec.mean


def test_llm_intrinsic_array_matches_kind():
    spec = ServiceSpec.llm(prefill=100.0, decode=5.0, gen_short=4.0,
                           gen_long=40.0, p_long=0.3)
    cfg = FleetConfig(n_servers=2, n_workers=2, service=spec)
    got = np.asarray(_intrinsic(cfg, jnp.array([0.0, 0.29, 0.31, 0.99])))
    assert got.tolist() == [300.0, 300.0, 120.0, 120.0]


def test_service_spec_validation_rejects_bad_params():
    with pytest.raises(ValueError, match="mean"):
        ServiceSpec.exponential(0.0)
    with pytest.raises(ValueError, match="short"):
        ServiceSpec.bimodal(short=-1.0, long=50.0)
    with pytest.raises(ValueError, match="p_long"):
        ServiceSpec.bimodal(short=5.0, long=50.0, p_long=1.5)
    with pytest.raises(ValueError, match="decode"):
        ServiceSpec.llm(decode=0.0)
    with pytest.raises(ValueError, match="xm"):
        ServiceSpec.pareto(xm=10.0, alpha=1.5, cap=10.0)
    with pytest.raises(ValueError, match="jitter_p"):
        ServiceSpec.exponential(25.0, jitter_p=1.5)
    with pytest.raises(ValueError, match="jitter_mult"):
        ServiceSpec.exponential(25.0, jitter_mult=0.0)
    # boundary values the property tests generate are all legal
    ServiceSpec.bimodal(short=5.0, long=50.0, p_long=0.0)
    ServiceSpec.bimodal(short=5.0, long=50.0, p_long=1.0)
    ServiceSpec.llm(prefill=0.0)
    ServiceSpec.exponential(25.0, jitter_p=0.0, jitter_mult=1.0)


def test_llm_process_validation():
    with pytest.raises(ValueError):
        LLMBimodalService(decode=-1.0)
    with pytest.raises(ValueError):
        LLMBimodalService(p_long=2.0)


# ------------------------------------------------------ roofline derivation --
def test_llm_service_is_roofline_derived():
    from repro.analysis.roofline import HBM_BW, n_params_active
    from repro.configs import get_config

    dec = decode_step_us("gemma-7b")
    _, active = n_params_active(get_config("gemma-7b"))
    # dense decode is memory-bound: the HBM term wins the roofline max
    assert dec == pytest.approx(2.0 * active / HBM_BW * 1e6)
    # prefill grows with prompt length once compute-bound
    assert prefill_us("gemma-7b", 4096) > prefill_us("gemma-7b", 128)
    with pytest.raises(ValueError, match="prompt_len"):
        prefill_us("gemma-7b", 0)
    # MoE activates a fraction of its parameters → cheaper per token
    assert decode_step_us("deepseek-moe-16b") < dec
    spec = llm_service("gemma-7b", prompt_len_dist=128,
                       gen_len_dist=("bimodal", 8, 64, 0.10))
    assert spec.kind == "llm"
    assert spec.params[0] == pytest.approx(prefill_us("gemma-7b", 128))
    assert spec.params[1] == pytest.approx(dec)


# --------------------------------------------------------------- config -----
def test_batch_config_validation():
    spec = ServiceSpec.exponential(25.0)
    with pytest.raises(ValueError, match="server_model"):
        FleetConfig(n_servers=2, n_workers=2, service=spec,
                    server_model="lifo")
    with pytest.raises(ValueError, match="batch_slots"):
        FleetConfig(n_servers=2, n_workers=2, service=spec, batch_slots=-1)
    cfg = FleetConfig(n_servers=2, n_workers=4, service=spec,
                      server_model="batch")
    assert cfg.n_slots == 4
    assert replace(cfg, batch_slots=6).n_slots == 6


# --------------------------------------------- fcfs golden / batch == fcfs --
def test_fcfs_golden_bit_identical():
    """An explicit server_model="fcfs" runs the exact golden program —
    the batch stage is compiled out, not branched around."""
    g = json.loads(GOLDEN.read_text())
    svc = ServiceSpec.exponential(25.0)
    cfg = FleetConfig(service=svc, server_model="fcfs", **g["cfg"])
    proc = svc.to_process()
    for c in g["cases"]:
        if "slowdown" in c or "fail_window" in c:
            continue
        rate = load_to_rate(c["load"], proc, cfg.n_servers, cfg.n_workers)
        params = make_params(cfg, POLICY_IDS[c["policy"]], rate, c["seed"])
        m = jax.block_until_ready(simulate(cfg, params))
        for field, want in c["metrics"].items():
            got = np.asarray(getattr(m, field)).reshape(-1)
            assert np.array_equal(got, np.asarray(want).reshape(-1)), \
                (c["policy"], field)


def test_batch_equals_fcfs_at_zero_coupling():
    """With independent slots (coupling=0) and one slot per worker, the
    batch stage's arithmetic is the FCFS ring's: every row of the sweep
    matches on every counter and latency statistic."""
    spec = ServiceSpec.bimodal(short=5.0, long=50.0, p_long=0.1,
                               jitter_p=0.01, jitter_mult=15.0)
    base = dict(n_servers=4, n_workers=2, n_ticks=2_000, service=spec)
    pols = ["baseline", "c-clone", "netclone", "racksched",
            "netclone+racksched"]
    loads, seeds = [0.4, 0.8], [0]
    fc = sweep_grid(spec, pols, loads, seeds, cfg=FleetConfig(**base))
    bt = sweep_grid(spec, pols, loads, seeds,
                    cfg=FleetConfig(**base, server_model="batch"))
    for rf, rb in zip(fc.results, bt.results):
        for k, v in rf.row().items():
            if k == "slot_occupancy":
                continue            # fcfs reports 0.0 by construction
            assert rb.row()[k] == v, (rf.policy, rf.offered_load, k)
        assert rb.mean_slot_occupancy > 0


def test_batch_occupancy_tracks_load():
    spec = ServiceSpec.exponential(25.0, jitter_p=0.0, jitter_mult=1.0)
    cfg = FleetConfig(n_servers=4, n_workers=4, n_ticks=3_000, service=spec,
                      server_model="batch")
    sw = sweep_grid(spec, ["baseline"], [0.3, 0.7], [0], cfg=cfg)
    occ = [r.mean_slot_occupancy for r in sw.results]
    assert occ[0] < occ[1]
    assert occ[0] == pytest.approx(0.3, abs=0.1)
    assert occ[1] == pytest.approx(0.7, abs=0.1)


# ------------------------------------------------------------- scenarios ----
def test_scenario_batch_fields_roundtrip():
    sc = Scenario(name="t", servers=2, workers=4, n_ticks=500,
                  service=ServiceSpec.llm(), server_model="batch",
                  batch_slots=6, batch_coupling=0.5, dt_us=10.0)
    assert Scenario.from_json(sc.to_json()) == sc
    cfg = sc.fleet_config()
    assert cfg.server_model == "batch" and cfg.n_slots == 6
    assert cfg.batch_coupling == 0.5 and cfg.dt_us == 10.0
    with pytest.raises(ValueError, match="unknown scenario keys"):
        Scenario.from_json({**sc.to_json(), "batch_slot": 1})
    with pytest.raises(ValueError, match="batch_slots"):
        Scenario(name="t", batch_slots=4).fleet_config()
    with pytest.raises(ValueError, match="DES models FCFS"):
        sc.run_des()


def test_bundled_llm_scenarios_load_and_run():
    for name in ("llm_gemma7b", "llm_moe_hetero"):
        sc = Scenario.from_file(name)
        assert sc.server_model == "batch"
        assert sc.service.kind == "llm"
        # dt is pinned to the per-token decode cost: one tick = one token
        assert sc.dt_us == pytest.approx(sc.service.params[1], rel=1e-4)
        r = sc.run_fleetsim(n_ticks=300)
        assert r.n_completed > 0
        assert r.mean_slot_occupancy > 0


# ---------------------------------------------------------------- oracle ----
def test_serve_equivalence_smoke():
    """The batch stage vs the real-model DecodeReplica oracle (small
    horizon; the run is deterministic, so tolerance passes are stable)."""
    from repro.fleetsim.validate import serve_equivalence

    checks = serve_equivalence(policies=("baseline", "netclone"),
                               loads=(0.4,), horizon=400)
    assert len(checks) == 2
    for c in checks:
        assert c.ok, c.describe()
        assert c.slot_occupancy > 0
