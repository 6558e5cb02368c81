"""Public jit'd wrappers for the Pallas kernels.

The kernels target TPU.  On a CPU backend (the test suite) they run in
Pallas ``interpret`` mode — Python-evaluated kernel bodies, bit-exact
semantics, not speed; on any other non-TPU backend they refuse to run
(:func:`interpret_mode`), so a kernel is never silently interpreted where a
compiled one was expected.  Model code calls through these wrappers with
``impl="auto"``, which picks the real kernel on TPU backends and the
pure-XLA reference otherwise, so the 512-device dry-run lowers plain XLA HLO
while the kernels stay the TPU hot-spot implementation.  The switch kernels
(fingerprint filter, TickFuse) have no XLA twin here — the FleetSim engine
selects them explicitly through ``FleetConfig.filter_backend``.
"""

from __future__ import annotations

import jax

from repro.kernels import ref as _ref
from repro.kernels.fingerprint_filter import fingerprint_filter as _fingerprint_filter
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.lru_scan import lru_scan as _lru_scan
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan
from repro.kernels.tickfuse import tickfuse_response_path as _tickfuse


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted on this process's backend:
    False on TPU (compiled), True on CPU, and an error anywhere else."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and are interpreted only on "
        f"CPU; backend {backend!r} has neither (run with JAX_PLATFORMS=cpu "
        f"or pick a non-kernel implementation)")


def attention(q, k, v, *, causal=True, window=None, sm_scale=None,
              impl: str = "auto", block_q: int = 256, block_k: int = 256):
    """Multi-head attention; q (B,H,S,D), k/v (B,Hkv,S,D)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  sm_scale=sm_scale)
    return _flash_attention(q, k, v, causal=causal, window=window,
                            sm_scale=sm_scale, block_q=block_q,
                            block_k=block_k, interpret=interpret_mode())


def fingerprint_filter(tables, req_id, idx, clo, *, block: int = 256):
    """NetClone response filter tick; returns (new_tables, drop_mask)."""
    return _fingerprint_filter(tables, req_id, idx, clo, block=block,
                               interpret=interpret_mode())


def tickfuse_response_path(server_state, tables, req_id, idx, clo, sid,
                           qlen):
    """Fused FleetSim switch response path (StateT write + fingerprint
    filter in one launch); returns (new_server_state, new_tables,
    drop_mask)."""
    return _tickfuse(server_state, tables, req_id, idx, clo, sid, qlen,
                     interpret=interpret_mode())


def ssd_scan(x, a, b_mat, c_mat, h0=None, *, impl: str = "auto",
             chunk: int = 128):
    """mamba2 SSD scan; returns (y, final_state)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        return _ref.ssd_scan_ref(x, a, b_mat, c_mat, h0)
    return _ssd_scan(x, a, b_mat, c_mat, h0, chunk=chunk,
                     interpret=interpret_mode())


def lru_scan(x, a, h0=None, *, impl: str = "auto", chunk: int = 256,
             block_d: int = 128):
    """RG-LRU diagonal recurrence; returns (y, final_state)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        return _ref.lru_scan_ref(x, a, h0)
    return _lru_scan(x, a, h0, chunk=chunk, block_d=block_d,
                     interpret=interpret_mode())
