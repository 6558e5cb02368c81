"""TickFuse (the fused core) and the one ``simulate`` entry point.

Four contracts:

* the fused core is **bit-identical** to the staged core on the
  non-stage policy matrix (baseline / c-clone / netclone / racksched /
  netclone+racksched), for every rack count, filter backend (including the
  Pallas TickFuse megakernel in interpret mode), and chunk length — and
  against the checked-in PR-1 goldens;
* dtype packing (``pick_count_dtype`` / ``pack_array``) widens or raises,
  never wraps: an exact integer round-trip for every in-bound value
  (property-tested);
* ``engine.select_core`` alone picks the core: fused on an accelerator for
  a config without an optional stage, staged everywhere else.  The tests
  fake the platform it reads (``tests/_cores.py``);
* ``shard`` is the one execution argument, and scenario and sweep files
  carry no ``engine`` key.
"""

import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _cores import use_core
from _hypothesis_compat import given, settings, st

from repro.core.workloads import ExponentialService, load_to_rate
from repro.fleetsim import (
    POLICY_IDS,
    FleetConfig,
    ServiceSpec,
    make_params,
    select_core,
    simulate,
)
from repro.fleetsim.engine import PinnedPrng
from repro.fleetsim.fused import (
    fused_core,
    pack_array,
    pack_state,
    pick_count_dtype,
    unpack_state,
)
from repro.fleetsim.state import init_fleet_state

SVC = ExponentialService(25.0)
GOLDEN = Path(__file__).parent / "golden" / "fleetsim_single_tor.json"

#: the fused-supported policy matrix (no coordinator / hedge_timer stage)
FUSED_POLICIES = ("baseline", "c-clone", "netclone", "racksched",
                  "netclone+racksched")


def fused_cfg(n_racks=1, **kw):
    base = dict(n_racks=n_racks, n_servers=4, n_workers=8, queue_cap=64,
                max_arrivals=10, n_ticks=900,
                service=ServiceSpec.exponential(25.0))
    base.update(kw)
    return FleetConfig(**base)


def run_params(cfg, policy, load=0.5, seed=0, **kw):
    rate = load_to_rate(load, SVC, cfg.n_servers_total, cfg.n_workers)
    return make_params(cfg, POLICY_IDS[policy], rate, seed, **kw)


def assert_tree_equal(a, b, what=""):
    for (pa, la), (pb, lb) in zip(
            jax.tree_util.tree_leaves_with_path(a),
            jax.tree_util.tree_leaves_with_path(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), \
            f"{what}: leaf {jax.tree_util.keystr(pa)} differs"


# ------------------------------------------------- fused == staged, bitwise --
@pytest.mark.parametrize("n_racks", [1, 2])
@pytest.mark.parametrize("policy", FUSED_POLICIES)
def test_fused_bit_identical_to_staged(policy, n_racks, monkeypatch):
    """Same ticks, same draws, same bits: the fused core replays the
    staged program exactly on the whole non-stage policy matrix."""
    cfg = fused_cfg(n_racks=n_racks)
    params = run_params(cfg, policy, load=0.6, seed=3)
    staged = simulate(cfg, params)
    use_core(monkeypatch, "fused")
    fused = simulate(cfg, params)
    assert_tree_equal(staged, fused, f"{policy}/racks={n_racks}")


@pytest.mark.parametrize("policy", ["netclone", "netclone+racksched"])
def test_fused_tickfuse_kernel_bit_identical(policy, monkeypatch):
    """The Pallas TickFuse switch megakernel (interpret mode on CPU) slots
    into the fused core with bit-identical results to the vectorized
    filter path."""
    cfg = fused_cfg(n_racks=2)
    params = run_params(cfg, policy, load=0.6, seed=5)
    staged = simulate(cfg, params)
    use_core(monkeypatch, "fused")
    cfg_tf = replace(cfg, filter_backend="tickfuse")
    fused = simulate(cfg_tf, params)
    assert_tree_equal(staged, fused, policy)


@pytest.mark.parametrize("k", [1, 7, 256, 10_000])
def test_fused_chunk_length_invariant(k):
    """K only moves the pack points: every chunk length (including a prime
    with a tail remainder and one clipped to n_ticks) is bit-identical."""
    cfg = fused_cfg(n_racks=2)
    params = run_params(cfg, "netclone", load=0.7, seed=1)
    ref = simulate(cfg, params)
    # the engine always runs the default K; the core takes any
    out = PinnedPrng(jax.jit(
        lambda p: fused_core(cfg, p, False, k).metrics))(params)
    assert_tree_equal(ref, out, f"K={k}")


def test_fused_bit_identical_to_golden(monkeypatch):
    """The fused core reproduces the PR-1 single-ToR goldens bit for bit
    (every checked-in case is a non-stage policy; 2,000 ticks are three
    chunks and a tail)."""
    use_core(monkeypatch, "fused")
    g = json.loads(GOLDEN.read_text())
    cfg = FleetConfig(service=ServiceSpec.exponential(25.0), **g["cfg"])
    for c in g["cases"]:
        rate = load_to_rate(c["load"], SVC, cfg.n_servers, cfg.n_workers)
        kw = {}
        if "slowdown" in c:
            kw["slowdown"] = np.asarray(c["slowdown"], np.float32)
        if "fail_window" in c:
            kw["fail_window"] = tuple(c["fail_window"])
        params = make_params(cfg, POLICY_IDS[c["policy"]], rate, c["seed"],
                             **kw)
        m = simulate(cfg, params)
        for field, want in c["metrics"].items():
            got = np.asarray(getattr(m, field)).reshape(-1)
            assert np.array_equal(got, np.asarray(want).reshape(-1)), \
                (c["policy"], field)


def test_fused_core_rejects_staged_only_stages():
    cfg = fused_cfg(coordinator=True)
    params = run_params(cfg, "laedge")
    with pytest.raises(ValueError, match="staged"):
        fused_core(cfg, params)


# --------------------------------------------------------- dtype packing ----
def test_pick_count_dtype_tiers():
    assert pick_count_dtype(0) == jnp.uint8
    assert pick_count_dtype(255) == jnp.uint8
    assert pick_count_dtype(256) == jnp.int16
    assert pick_count_dtype(32767) == jnp.int16
    assert pick_count_dtype(32768) == jnp.int32
    assert pick_count_dtype(2**31 - 1) == jnp.int32
    with pytest.raises(ValueError, match="wrap"):
        pick_count_dtype(2**31)
    with pytest.raises(ValueError, match="non-negative"):
        pick_count_dtype(-1)


@settings(max_examples=80, deadline=None)
@given(st.integers(min_value=0, max_value=2**33))
def test_pack_never_wraps(bound):
    """Raises-or-widens-never-wraps: any bound either gets a dtype that
    round-trips every value in [0, bound] exactly, or a ValueError."""
    try:
        dt = pick_count_dtype(bound)
    except ValueError:
        assert bound > 2**31 - 1
        return
    assert bound <= jnp.iinfo(dt).max
    probe = np.unique(np.clip([0, 1, bound // 2, bound - 1, bound],
                              0, bound)).astype(np.int64)
    packed = pack_array(jnp.asarray(probe, jnp.int32), bound)
    assert packed.dtype == dt
    assert np.array_equal(np.asarray(packed.astype(jnp.int64)), probe)


def test_pack_state_round_trip():
    """pack → unpack restores the exact int32 state, and the packed carry
    uses narrow dtypes for a small queue_cap."""
    cfg = fused_cfg(queue_cap=32)
    state = init_fleet_state(cfg, jax.random.PRNGKey(0))
    packed = pack_state(cfg, state)
    assert packed.queues.head.dtype == jnp.uint8
    assert packed.queues.count.dtype == jnp.uint8
    assert packed.switch.server_state.dtype == jnp.uint8
    # REQ_ID carriers stay int32 — a packed req-id would alias requests
    assert packed.switch.filter_tables.dtype == jnp.int32
    assert_tree_equal(state, unpack_state(packed), "pack round-trip")


# -------------------------------------------------- the one execution path --
STAGES = {"plain": {}, "coordinator": dict(coordinator=True),
          "hedge": dict(hedge_timer=True),
          "telemetry": dict(telemetry=True, window_ticks=100),
          "batch": dict(server_model="batch")}


@pytest.mark.parametrize("stage", list(STAGES))
@pytest.mark.parametrize("platform", ["cpu", "tpu", "gpu"])
def test_select_core(platform, stage, monkeypatch):
    """Fused exactly on an accelerator for a config without an optional
    stage; staged for every stage and everywhere on the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    want = "fused" if platform != "cpu" and stage == "plain" else "staged"
    assert select_core(fused_cfg(**STAGES[stage])) == want


def _batchify(cfg, policies):
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[run_params(cfg, p, seed=i)
                          for i, p in enumerate(policies)])


def test_options_validation():
    """``shard`` refuses what the sharded runner cannot run: a telemetry
    config, and scalar params."""
    tel = fused_cfg(telemetry=True, window_ticks=100)
    with pytest.raises(ValueError, match="sharded runner"):
        simulate(tel, _batchify(tel, ["baseline", "netclone"]), shard=1)
    cfg = fused_cfg()
    with pytest.raises(ValueError, match="leading sweep axis"):
        simulate(cfg, run_params(cfg, "netclone"), shard=1)


def test_scenario_and_sweep_files_refuse_engine_key(tmp_path):
    """Files are the API: an ``engine`` key (the execution knobs this repo
    no longer has) is refused by the strict-key check, which names it."""
    from repro.scenarios import Scenario, SweepSpec

    sc = Scenario(name="t", n_ticks=500)
    sc_file = tmp_path / "scenario.json"
    sc_file.write_text(json.dumps({**sc.to_json(),
                                   "engine": {"backend": "fused"}}))
    with pytest.raises(ValueError, match=r"unknown scenario keys \['engine'\]"):
        Scenario.from_file(sc_file)
    sp = SweepSpec(base=sc, policies=("baseline",))
    sp_file = tmp_path / "sweep.json"
    sp_file.write_text(json.dumps({**sp.to_json(),
                                   "engine": {"backend": "staged"}}))
    with pytest.raises(ValueError, match=r"unknown sweep keys \['engine'\]"):
        SweepSpec.from_file(sp_file)
    assert SweepSpec.from_file(sp.to_file(tmp_path / "ok.json")) == sp


def test_simulate_rejects_bad_params_shapes():
    cfg = fused_cfg()
    params = run_params(cfg, "netclone")
    bad = jax.tree.map(lambda a: jnp.stack([jnp.stack([a, a])] * 2), params)
    with pytest.raises(ValueError, match="scalar .*or 1-D"):
        simulate(cfg, bad)
    with pytest.raises(TypeError, match="shard must be"):
        simulate(cfg, _batchify(cfg, ["netclone"]), shard="fused")


def test_no_warning_on_unified_path(recwarn):
    """The entry point raises no DeprecationWarning."""
    cfg = fused_cfg()
    simulate(cfg, run_params(cfg, "netclone"))
    assert not [w for w in recwarn.list
                if issubclass(w.category, DeprecationWarning)]


# ------------------------------------------------------------ unified misc --
def test_unified_fused_batch_and_donate(monkeypatch):
    """Batched fused runs match per-run staged, and the call donates
    nothing: the caller's params stay usable after it."""
    cfg = fused_cfg(n_racks=2)
    grid = _batchify(cfg, ["netclone", "racksched", "baseline"])
    ref = simulate(cfg, grid)
    use_core(monkeypatch, "fused")
    out = simulate(cfg, grid)
    assert_tree_equal(ref, out, "fused batch")
    assert_tree_equal(ref, simulate(cfg, grid), "fused batch, params reused")


def test_lower_compiles_every_backend(monkeypatch):
    from repro.fleetsim import lower

    cfg = fused_cfg()
    params = run_params(cfg, "netclone")
    for core in ("staged", "fused"):
        use_core(monkeypatch, core)
        compiled = lower(cfg, params).compile()
        m = jax.block_until_ready(compiled(params))
        assert int(m.n_arrivals) > 0


def test_sweep_backend_recorded(monkeypatch):
    from repro.fleetsim import sweep_grid

    res2 = sweep_grid(SVC, ["baseline"], [0.5], [0], n_racks=1, n_ticks=500)
    assert res2.backend == "staged"  # the CPU
    use_core(monkeypatch, "fused")
    res = sweep_grid(SVC, ["baseline"], [0.5], [0], n_racks=1, n_ticks=500)
    assert res.backend == "fused"
    assert [r.row() for r in res.results] == [r.row() for r in res2.results]
