"""Steer ``engine.select_core`` from a test by faking the platform it reads."""

import jax

from repro.kernels import ops


def use_core(monkeypatch, core: str) -> None:
    """Make the engine select ``core`` for a config without an optional
    stage: ``'fused'`` is chosen on an accelerator, so the platform reads
    as a TPU, and ``'staged'`` on the CPU.  The process is still a CPU, so
    the Pallas kernels stay interpreted."""
    assert core in ("staged", "fused"), core
    platform = "tpu" if core == "fused" else "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    monkeypatch.setattr(ops, "interpret_mode", lambda: True)
