"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Every Pallas kernel is swept over shapes/dtypes and hypothesis-driven random
streams; the oracles themselves are cross-validated against step-by-step
naive recurrences.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.switch_jax import filter_tick_oracle
from repro.fleetsim import FleetConfig
from repro.kernels import ops, ref
from repro.kernels.fingerprint_filter import fingerprint_filter
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lru_scan import lru_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.tickfuse import tickfuse_response_path


# ========================================================= flash attention ===
@pytest.mark.parametrize(
    "b,h,hkv,s,d,causal,window,dtype",
    [
        (1, 4, 4, 256, 64, True, None, jnp.float32),
        (2, 8, 2, 256, 64, True, None, jnp.float32),      # GQA
        (1, 4, 1, 256, 128, True, None, jnp.float32),     # MQA
        (1, 4, 4, 512, 64, False, None, jnp.float32),     # bidirectional
        (1, 2, 2, 512, 64, True, 128, jnp.float32),       # sliding window
        (1, 2, 2, 256, 64, True, None, jnp.bfloat16),     # bf16
        (3, 2, 2, 128, 32, True, None, jnp.float32),      # odd batch
    ],
)
def test_flash_attention_matches_oracle(b, h, hkv, s, d, causal, window,
                                        dtype):
    rng = np.random.default_rng(hash((b, h, s, d)) % 2 ** 31)
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_k=128)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_flash_attention_block_shape_independence():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk)
            for bq, bk in [(64, 64), (128, 256), (256, 128), (512, 512)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   atol=1e-5)


def test_attention_ref_chunked_equals_direct():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 2, 512, 32)), jnp.float32)
    k, v = q + 0.1, q - 0.1
    direct = ref.attention_ref(q, k, v, causal=True)
    old_thr, old_chunk = ref.ATTN_CHUNK_THRESHOLD, ref.ATTN_Q_CHUNK
    try:
        ref.ATTN_CHUNK_THRESHOLD, ref.ATTN_Q_CHUNK = 128, 128
        chunked = ref.attention_ref(q, k, v, causal=True)
    finally:
        ref.ATTN_CHUNK_THRESHOLD, ref.ATTN_Q_CHUNK = old_thr, old_chunk
    np.testing.assert_allclose(np.asarray(direct), np.asarray(chunked),
                               atol=1e-5)


# ====================================================== fingerprint filter ===
@given(
    data=st.lists(
        st.tuples(st.integers(1, 40), st.integers(0, 1), st.integers(0, 2)),
        min_size=1, max_size=200),
    block=st.sampled_from([32, 64, 128]),
)
@settings(max_examples=25, deadline=None)
def test_fingerprint_filter_property(data, block):
    """Kernel ≡ sequential oracle for arbitrary interleavings (duplicates,
    collisions, CLO=0 passthrough, cross-block carry of table state)."""
    rid = np.array([d[0] for d in data], np.int64)
    idx = np.array([d[1] for d in data], np.int64)
    clo = np.array([d[2] for d in data], np.int64)
    tables = np.zeros((2, 128), np.int32)
    got_t, got_d = fingerprint_filter(
        jnp.asarray(tables), jnp.asarray(rid, jnp.int32),
        jnp.asarray(idx, jnp.int32), jnp.asarray(clo, jnp.int32), block=block,
        interpret=True)
    want_t, _, want_d = filter_tick_oracle(
        tables.astype(np.int64), np.zeros(1, np.int64), rid, idx, clo,
        np.zeros(len(rid), int), np.zeros(len(rid), int))
    assert np.array_equal(np.asarray(got_d), want_d)
    assert np.array_equal(np.asarray(got_t), want_t.astype(np.int32))


def test_fingerprint_filter_table_sizes():
    # collision-free sizes: every twin response is filtered
    for n_slots in (1024, 4096):
        tables = jnp.zeros((2, n_slots), jnp.int32)
        rid = jnp.arange(1, 129, dtype=jnp.int32)
        t, d = fingerprint_filter(tables, rid, rid % 2, jnp.ones(128, jnp.int32),
                                  interpret=True)
        assert not bool(d.any())          # fresh ids are never dropped
        t2, d2 = fingerprint_filter(t, rid, rid % 2, jnp.ones(128, jnp.int32),
                                    interpret=True)
        assert bool(d2.all())             # every twin is dropped
    # tiny table: collisions overwrite — some twins escape, none misfire
    tables = jnp.zeros((2, 64), jnp.int32)
    rid = jnp.arange(1, 129, dtype=jnp.int32)
    t, d = fingerprint_filter(tables, rid, rid % 2, jnp.ones(128, jnp.int32),
                              interpret=True)
    assert not bool(d.any())
    t2, d2 = fingerprint_filter(t, rid, rid % 2, jnp.ones(128, jnp.int32),
                                interpret=True)
    assert bool(d2.any()) and not bool(d2.all())


@pytest.mark.parametrize("vmapped", [False, True], ids=["once", "vmapped"])
def test_tickfuse_matches_sequential_oracle(vmapped):
    """StateT + filter in lane order, duplicates and same-tick slot
    collisions included; under vmap the lanes are per fabric while the
    resident tables start shared (the custom_vmap rule broadcasts them)."""
    rng = np.random.default_rng(4)
    n_fab, lanes, n_srv = 3, 40, 5
    tables = np.zeros((3, 16), np.int32)       # 16 slots: collisions
    rid = rng.integers(1, 25, (n_fab, lanes))  # repeats: twin responses
    idx = rng.integers(0, 3, (n_fab, lanes))
    clo = rng.integers(0, 3, (n_fab, lanes))
    sid = rng.integers(0, n_srv + 1, (n_fab, lanes))  # n_srv: inactive
    qlen = rng.integers(0, 9, (n_fab, lanes))
    state = np.zeros(n_srv, np.int32)

    def run(r, i, c, s, q):
        return tickfuse_response_path(
            jnp.asarray(state), jnp.asarray(tables), r, i, c, s, q,
            interpret=True)

    args = [jnp.asarray(a, jnp.int32) for a in (rid, idx, clo, sid, qlen)]
    if vmapped:
        got = jax.vmap(run)(*args)
    else:
        got = jax.tree.map(lambda *a: jnp.stack(a),
                           *[run(*(a[f] for a in args))
                             for f in range(n_fab)])
    for f in range(n_fab):
        # the oracle has a slot for the inactive sid; drop it afterwards
        want_t, want_s, want_d = filter_tick_oracle(
            tables.astype(np.int64), np.zeros(n_srv + 1, np.int64),
            rid[f], idx[f], clo[f], sid[f], qlen[f])
        assert np.array_equal(np.asarray(got[0][f]), want_s[:n_srv])
        assert np.array_equal(np.asarray(got[1][f]), want_t)
        assert np.array_equal(np.asarray(got[2][f]), want_d)


def test_switch_kernels_refuse_tables_past_smem():
    """A fabric whose filter tables cannot fit the kernels' SMEM is refused
    at config construction, naming the limit — never run on another
    backend in silence."""
    assert FleetConfig(n_racks=123, filter_backend="tickfuse").n_racks == 123
    with pytest.raises(ValueError, match="SMEM_LIMIT_BYTES"):
        FleetConfig(n_racks=124, filter_backend="tickfuse")
    with pytest.raises(ValueError, match="SMEM_LIMIT_BYTES"):
        FleetConfig(n_filter_slots=2 ** 17, filter_backend="pallas")
    # the vectorized filter keeps its tables in HBM: no such bound
    assert FleetConfig(n_filter_slots=2 ** 17).n_filter_slots == 2 ** 17


def test_interpret_mode_only_on_cpu(monkeypatch):
    """Kernels interpret on CPU, compile on TPU, and refuse elsewhere."""
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops.interpret_mode() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()


# ================================================================ SSD scan ===
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 256, 2, 64, 64, 64),
    (2, 128, 1, 32, 128, 128),
    (1, 512, 3, 16, 32, 128),
])
def test_ssd_kernel_vs_naive(b, s, h, p, n, chunk):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.2, 1.0, (b, s, h)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, s, h, n)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, s, h, n)) * 0.3, jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((b, h, p, n)) * 0.1, jnp.float32)
    yk, hk = ssd_scan(x, a, bm, cm, h0, chunk=chunk)
    yn, hn = ref.ssd_scan_naive(x, a, bm, cm, h0)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yn), atol=2e-3)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hn), atol=2e-3)


def test_ssd_chunked_ref_vs_naive():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 256, 2, 32)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.3, 1.0, (2, 256, 2)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((2, 256, 2, 64)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.standard_normal((2, 256, 2, 64)) * 0.3, jnp.float32)
    y1, h1 = ref.ssd_scan_ref(x, a, bm, cm, chunk=64)
    y2, h2 = ref.ssd_scan_naive(x, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-3)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-3)


@given(seed=st.integers(0, 100), decay_lo=st.floats(0.05, 0.9))
@settings(max_examples=15, deadline=None)
def test_ssd_property_random_streams(seed, decay_lo):
    rng = np.random.default_rng(seed)
    b, s, h, p, n = 1, 128, 2, 16, 16
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    a = jnp.asarray(rng.uniform(decay_lo, 1.0, (b, s, h)), jnp.float32)
    bm = jnp.asarray(rng.standard_normal((b, s, h, n)) * 0.2, jnp.float32)
    cm = jnp.asarray(rng.standard_normal((b, s, h, n)) * 0.2, jnp.float32)
    yk, hk = ssd_scan(x, a, bm, cm, chunk=32)
    yn, hn = ref.ssd_scan_naive(x, a, bm, cm)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yn), atol=3e-3)


# ================================================================ LRU scan ===
@pytest.mark.parametrize("b,s,d,chunk,bd", [
    (2, 256, 256, 128, 128),
    (1, 512, 128, 256, 128),
    (1, 128, 384, 64, 128),
])
def test_lru_kernel_vs_naive(b, s, d, chunk, bd):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((b, s, d)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.5, 1.0, (b, s, d)), jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((b, d)) * 0.1, jnp.float32)
    yk, hk = lru_scan(x, a, h0, chunk=chunk, block_d=bd)
    yn, hn = ref.lru_scan_naive(x, a, h0)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yn), atol=1e-4)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hn), atol=1e-4)


def test_lru_associative_ref_vs_naive():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 333, 32)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.4, 1.0, (2, 333, 32)), jnp.float32)
    y1, h1 = ref.lru_scan_ref(x, a)
    y2, h2 = ref.lru_scan_naive(x, a)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)
