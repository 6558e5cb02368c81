"""Multi-rack fabric: bit-identity, inter-rack filtering, rack skew.

Three contracts from the 2-tier extension:

* ``n_racks == 1`` is **bit-identical** to the pre-fabric single-ToR engine
  — enforced against golden metrics captured from that engine
  (``tests/golden/fleetsim_single_tor.json``), covering every policy plus
  straggler and switch-failure injection;
* inter-rack clone pairs are filtered **exactly once** per (req_id, idx)
  group at the spine, whichever order and tick their responses arrive in;
* rack-skew injection (hot rack / straggler rack) engages inter-rack
  cloning and the per-rack metrics expose it.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.switch_jax import (
    filter_rows,
    filter_tick_oracle,
    fingerprint_hash_jax,
)
from repro.core.workloads import ExponentialService, load_to_rate
from repro.fleetsim import (
    POLICY_IDS,
    FleetConfig,
    ServiceSpec,
    make_params,
    rack_skew,
    simulate,
    summarize,
)
from repro.fleetsim.sweep import sweep_grid

SVC = ExponentialService(25.0)
GOLDEN = Path(__file__).parent / "golden" / "fleetsim_single_tor.json"


def fabric_cfg(n_racks=2, **kw):
    base = dict(n_racks=n_racks, n_servers=4, n_workers=8, queue_cap=64,
                max_arrivals=10, n_ticks=4000,
                service=ServiceSpec.exponential(25.0))
    base.update(kw)
    return FleetConfig(**base)


def run(policy, load=0.4, seed=0, cfg=None, **param_kw):
    cfg = cfg or fabric_cfg()
    rate = load_to_rate(load, SVC, cfg.n_servers_total, cfg.n_workers)
    params = make_params(cfg, POLICY_IDS[policy], rate, seed, **param_kw)
    return cfg, jax.block_until_ready(simulate(cfg, params))


# ----------------------------------------------------- golden bit-identity --
def _golden():
    return json.loads(GOLDEN.read_text())


@pytest.mark.parametrize("case_i", range(len(_golden()["cases"])))
def test_nracks1_bit_identical_to_single_tor_engine(case_i):
    """The fabric with one rack replays the pre-fabric engine draw for draw:
    every metric (including the full latency histogram) is bit-identical to
    goldens captured from the single-ToR engine at PR 1."""
    g = _golden()
    cfg = FleetConfig(service=ServiceSpec.exponential(25.0), **g["cfg"])
    c = g["cases"][case_i]
    rate = load_to_rate(c["load"], SVC, cfg.n_servers, cfg.n_workers)
    kw = {}
    if "slowdown" in c:
        kw["slowdown"] = np.asarray(c["slowdown"], np.float32)
    if "fail_window" in c:
        kw["fail_window"] = tuple(c["fail_window"])
    params = make_params(cfg, POLICY_IDS[c["policy"]], rate, c["seed"], **kw)
    m = jax.block_until_ready(simulate(cfg, params))
    for field, want in c["metrics"].items():
        got = np.asarray(getattr(m, field)).reshape(-1)
        assert np.array_equal(got, np.asarray(want).reshape(-1)), field


# ------------------------------------------- exactly-once inter-rack filter --
N_RACKS, N_TABLES, N_SLOTS = 2, 2, 1024
FABRIC = FleetConfig(n_racks=N_RACKS, n_filter_tables=N_TABLES,
                     n_filter_slots=N_SLOTS)


def _fabric_filter(tables, rid, idx, active=None):
    """One response tick through the fabric filter, exactly as the engine
    runs it (rack table groups + the spine group in the carried layout)."""
    rid = jnp.asarray(rid, jnp.int32)
    if active is None:
        active = jnp.ones(rid.shape, bool)
    tables, drop = filter_rows(
        tables, N_SLOTS, rid, jnp.asarray(idx, jnp.int32),
        jnp.ones(rid.shape, jnp.int32),            # CLO > 0: touches FilterT
        jnp.asarray(active))
    return tables, np.asarray(drop)


def _slot(rid):
    return int(fingerprint_hash_jax(jnp.int32(rid), N_SLOTS))


def _exactly_once(pairs):
    """Feed each (rid, row, split) pair's two responses through the fabric
    filter — same tick or split across two — and count drops per pair."""
    tables = jnp.zeros(FABRIC.filter_table_shape, jnp.int32)
    tick1, tick2 = [], []
    for rid, row, split in pairs:
        tick1.append((rid, row))
        (tick2 if split else tick1).append((rid, row))
    drops = {rid: 0 for rid, _, _ in pairs}
    for lanes in (tick1, tick2):
        if not lanes:
            continue
        rid = np.array([r for r, _ in lanes], np.int32)
        row = np.array([x for _, x in lanes], np.int32)
        tables, drop = _fabric_filter(tables, rid, row)
        for r, d in zip(rid, drop):
            drops[int(r)] += int(d)
    # every pair dropped exactly once; the stack fully drained
    assert all(n == 1 for n in drops.values()), drops
    assert int(jnp.sum(tables != 0)) == 0


def test_interrack_pairs_filtered_exactly_once_deterministic():
    rng = np.random.default_rng(0)
    used = set()
    pairs = []
    rid = 1
    while len(pairs) < 60:
        row = int(rng.integers(0, (N_RACKS + 1) * N_TABLES))
        key = (row, _slot(rid))
        if key not in used:         # avoid unrelated same-slot collisions
            used.add(key)
            pairs.append((rid, row, bool(rng.integers(0, 2))))
        rid += 1
    _exactly_once(pairs)


@given(st.lists(
    st.tuples(st.integers(min_value=1, max_value=2 ** 20),
              st.integers(min_value=0, max_value=(N_RACKS + 1) * N_TABLES - 1),
              st.booleans()),
    min_size=1, max_size=24, unique_by=lambda p: p[0]))
@settings(max_examples=50, deadline=None)
def test_interrack_pairs_filtered_exactly_once_property(pairs):
    """Property form: any mix of rack-local and spine (req_id, idx) groups,
    same-tick or split across ticks, drops each pair exactly once."""
    seen = set()
    kept = []
    for rid, row, split in pairs:
        key = (row, _slot(rid))
        if key not in seen:         # distinct slots ⇒ exact sequential match
            seen.add(key)
            kept.append((rid, row, split))
    _exactly_once(kept)


# ------------------------------------------- the carried filter-table layout --
def _carried(cfg, tables3d):
    """(group, table, slot) tables in the layout the engine carries."""
    return jnp.asarray(np.asarray(tables3d, np.int32)
                       .reshape(cfg.filter_table_shape))


def _random_lanes(rng, cfg, n_keys):
    """Lanes over every table of the fabric, spine group included: distinct
    ids on distinct (table, slot) cells, some keys repeated in the same
    tick, in a shuffled lane order."""
    n_rows, slots = (cfg.n_racks + 1) * cfg.n_filter_tables, \
        cfg.n_filter_slots
    rid, idx, cells = [], [], set()
    while len(rid) < n_keys:
        r, i = int(rng.integers(1, 2 ** 20)), int(rng.integers(0, n_rows))
        cell = (i, int(fingerprint_hash_jax(jnp.int32(r), slots)))
        if cell not in cells:  # no different-id collision in one tick
            cells.add(cell)
            rid.append(r)
            idx.append(i)
    dup = list(rng.choice(n_keys, n_keys // 4, replace=False))
    dup += dup[:3]             # some keys three times
    rid, idx = np.array(rid + [rid[k] for k in dup]), \
        np.array(idx + [idx[k] for k in dup])
    order = rng.permutation(len(rid))
    return rid[order], idx[order]


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("racks", [1, 4])
def test_carried_filter_matches_oracle_per_group(racks, seed):
    """The engine's filter over the carried layout (rows of 128 slots)
    drops and writes what the sequential switch does, group by group: the
    spine group of a 4-rack fabric, same-tick duplicate keys, fingerprints
    already parked, and masked or CLO=0 lanes, which write nothing."""
    cfg = FleetConfig(n_racks=racks, n_filter_slots=1024)
    G, T, S = racks + 1, cfg.n_filter_tables, cfg.n_filter_slots
    rng = np.random.default_rng(seed)
    rid, idx = _random_lanes(rng, cfg, 64)
    clo = rng.integers(0, 3, len(rid))
    active = rng.random(len(rid)) < 0.8
    tables = np.zeros((G * T, S), np.int64)
    for k in rng.choice(len(rid), 20):   # parked fingerprints and strangers
        slot = int(fingerprint_hash_jax(jnp.int32(int(rid[k])), S))
        tables[idx[k], slot] = rid[k] if k % 2 else rid[k] + 1
    new, drop = filter_rows(_carried(cfg, tables), S, jnp.asarray(rid),
                            jnp.asarray(idx), jnp.asarray(clo),
                            jnp.asarray(active))
    want, want_drop = tables.reshape(G, T, S).copy(), np.zeros(len(rid), bool)
    for g in range(G):
        lanes = np.flatnonzero(active & (idx // T == g))
        zero = np.zeros(len(lanes), np.int64)
        want[g], _, want_drop[lanes] = filter_tick_oracle(
            want[g], np.zeros(1, np.int64), rid[lanes], idx[lanes] % T,
            clo[lanes], zero, zero)
    touched = active & (clo > 0)
    assert (touched & (idx // T == racks)).any()         # the spine group
    assert np.array_equal(np.asarray(drop), want_drop)
    assert np.asarray(drop).any() and not np.asarray(drop)[~touched].any()
    assert np.array_equal(np.asarray(new).reshape(G, T, S), want)


def test_masked_lanes_write_nothing():
    """A tick whose lanes are all masked or CLO=0 leaves every table as it
    was and drops nothing, even where it would hit a parked fingerprint."""
    rng = np.random.default_rng(5)
    rid, idx = _random_lanes(rng, FABRIC, 32)
    tables = rng.integers(0, 2 ** 20, (FABRIC.filter_table_size,))
    slot = np.asarray(fingerprint_hash_jax(jnp.asarray(rid, jnp.int32),
                                           N_SLOTS))
    tables[idx * N_SLOTS + slot] = rid     # every lane's fingerprint parked
    carried = _carried(FABRIC, tables)
    active = np.arange(len(rid)) % 2 == 0
    clo = np.where(active, 0, 2)           # masked lanes would clone
    new, drop = filter_rows(carried, N_SLOTS, jnp.asarray(rid),
                            jnp.asarray(idx), jnp.asarray(clo),
                            jnp.asarray(active))
    assert not np.asarray(drop).any()
    assert np.array_equal(np.asarray(new), np.asarray(carried))


def test_filter_tables_must_fit_int32_indices():
    """The carried tables are indexed with int32: a fabric whose tables hold
    2^31 slots is refused, one just below passes."""
    ok = FleetConfig(n_racks=2, n_filter_tables=5, n_filter_slots=2 ** 27)
    assert ok.filter_table_size == 15 * 2 ** 27 < 2 ** 31
    with pytest.raises(ValueError, match="int32"):
        FleetConfig(n_racks=3, n_filter_tables=4, n_filter_slots=2 ** 27)


# --------------------------------------------------------- fabric behavior --
@pytest.mark.parametrize("backend", ["scan", "pallas"])
def test_fabric_filter_backends_match_vectorized(backend):
    """The flattened rack+spine table stack behaves identically under every
    filter backend, inter-rack pairs included."""
    cfg_kw = dict(n_ticks=2000, max_arrivals=8)
    _, ref = run("netclone", load=0.55, seed=7,
                 cfg=fabric_cfg(**cfg_kw),
                 rack_weights=[0.85, 0.15])
    _, alt = run("netclone", load=0.55, seed=7,
                 cfg=fabric_cfg(filter_backend=backend, **cfg_kw),
                 rack_weights=[0.85, 0.15])
    assert int(ref.n_interrack_cloned) > 0      # spine rows exercised
    for f in ref._fields:
        assert np.array_equal(np.asarray(getattr(ref, f)),
                              np.asarray(getattr(alt, f))), f


def test_multirack_conservation():
    for policy in ("baseline", "netclone", "netclone+racksched"):
        cfg, m = run(policy, load=0.5, rack_weights=[0.8, 0.2])
        n_arr = int(m.n_arrivals)
        assert n_arr > 0 and int(m.n_completed) > 0
        in_flight = cfg.n_servers_total * (cfg.n_workers + cfg.queue_cap) \
            + 2 * cfg.max_arrivals
        assert 0 <= n_arr - int(m.n_completed) - int(m.n_overflow) <= in_flight
        # clone bookkeeping, fabric-wide and per tier
        assert int(m.n_interrack_cloned) <= int(m.n_cloned)
        assert int(m.n_spine_filtered) <= int(m.n_filtered)
        assert int(m.n_filtered) <= int(m.n_cloned)
        # the spine only ever filters inter-rack pairs
        assert int(m.n_spine_filtered) <= int(m.n_interrack_cloned)
        # per-rack histograms partition the in-window completions
        assert int(np.asarray(m.hist).sum()) == int(m.n_completed_win)
        assert np.asarray(m.hist).shape == (cfg.n_racks, cfg.hist_bins)


def test_hot_rack_triggers_interrack_cloning():
    """With one hot rack the home ToR saturates while the cool rack stays
    tracked-idle — the spine must place clones across racks and filter their
    pairs; with uniform arrivals it mostly should not."""
    _, hot = run("netclone", load=0.55, rack_weights=[0.85, 0.15])
    assert int(hot.n_interrack_cloned) > 100
    assert int(hot.n_spine_filtered) > 0
    _, uniform = run("netclone", load=0.55)
    assert int(uniform.n_interrack_cloned) < int(hot.n_interrack_cloned) / 4
    # the cool rack absorbs a visible share of the hot rack's work
    served_cool = np.asarray(hot.hist).sum(axis=1)[1]
    assert served_cool > 0.15 * np.asarray(hot.hist).sum()


def test_interrack_cloning_cuts_hot_rack_tail():
    """§3.7: under rack skew, inter-rack cloning beats single-copy routing
    confined to the home rack."""
    cfg = fabric_cfg(n_ticks=8000)
    base = summarize(cfg, run("baseline", load=0.5, cfg=cfg,
                              rack_weights=[0.85, 0.15])[1],
                     policy="baseline", load=0.5, rate_per_us=0.0, seed=0)
    nc = summarize(cfg, run("netclone", load=0.5, cfg=cfg,
                            rack_weights=[0.85, 0.15])[1],
                   policy="netclone", load=0.5, rate_per_us=0.0, seed=0)
    assert nc.p99_us < base.p99_us
    assert nc.n_interrack_cloned > 0


def test_straggler_rack_skew_helper():
    cfg = fabric_cfg(n_racks=3)
    weights, slowdown = rack_skew(cfg, hot_rack_weight=2.0,
                                  straggler_rack_mult=3.0)
    assert weights.tolist() == [2.0, 1.0, 1.0]
    assert slowdown.shape == (cfg.n_servers_total,)
    assert slowdown.reshape(3, -1)[2].tolist() == [3.0] * cfg.n_servers
    _, m = run("netclone+racksched", load=0.4, cfg=cfg,
               rack_weights=weights, slowdown=slowdown)
    assert int(m.n_completed) > 0


def test_multirack_sweep_grid_per_rack_metrics():
    cfg = fabric_cfg(n_ticks=2500)
    weights, slowdown = rack_skew(cfg, hot_rack_weight=4.0)
    sw = sweep_grid(SVC, ["baseline", "netclone"], [0.45], [0, 1], cfg=cfg,
                    rack_weights=weights, slowdown=slowdown)
    assert sw.n_configs == 4
    for r in sw.results:
        assert len(r.rack_p99_us) == cfg.n_racks
        assert len(r.rack_completed) == cfg.n_racks
        assert sum(r.rack_completed) > 0
        assert "rack_p99_us" in r.row()
    nc = sw.select(policy="netclone")
    assert all(r.n_interrack_cloned > 0 for r in nc)


def test_fabric_config_validation():
    with pytest.raises(ValueError):
        FleetConfig(n_racks=0)
    cfg = fabric_cfg(n_racks=4)
    assert cfg.n_servers_total == 16
    assert cfg.spine_extra_us > 0 and cfg.interrack_extra_us > 0
    single = fabric_cfg(n_racks=1)
    assert single.spine_extra_us == 0.0 and single.interrack_extra_us == 0.0
    with pytest.raises(ValueError):
        make_params(cfg, 0, 1.0, 0, slowdown=np.ones(3, np.float32))
    with pytest.raises(ValueError):
        make_params(cfg, 0, 1.0, 0, rack_weights=np.ones(2, np.float32))
    with pytest.raises(ValueError):
        sweep_grid(SVC, ["baseline"], [0.2], [0], cfg=cfg,
                   rack_weights=np.ones(3, np.float32))


# ------------------------------------------------------- benchmark harness --
def test_benchmarks_run_rejects_unknown_args(monkeypatch, capsys):
    brun = pytest.importorskip("benchmarks.run")
    with pytest.raises(SystemExit) as exc:
        monkeypatch.setattr("sys.argv", ["run.py", "--engine", "nope"])
        brun.main()
    assert exc.value.code == 2
    with pytest.raises(SystemExit) as exc:
        monkeypatch.setattr("sys.argv", ["run.py", "no_such_figure"])
        brun.main()
    assert exc.value.code == 2
    assert "no_such_figure" in capsys.readouterr().err
