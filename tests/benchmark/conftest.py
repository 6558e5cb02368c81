"""Shared fixtures of the chip benchmark's CPU tests: the harness's own
directory on the path, cells cut to a size a test run holds, and the
reference run in the test's own process."""

import sys
from pathlib import Path

import pytest

PERFBENCH = Path(__file__).resolve().parents[2] / "perfbench"
sys.path.insert(0, str(PERFBENCH))

#: the test size: two policies (one that clones) at two loads, 2,048 ticks
TINY = dict(policies=["baseline", "netclone"], loads=[0.2, 0.65],
            n_ticks=2048)


@pytest.fixture
def tiny(monkeypatch):
    """``tiny(name)`` → the cell cut to :data:`TINY`; ``run.load_cell``
    then hands it to ``run.main`` too.  ``run.main`` turns JAX's
    persistent compilation cache on; it is turned off again afterwards,
    so later tests in the same process compile as they always did."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import run
    from reference import pool

    monkeypatch.setattr(pool, "MAX_WORKERS", 0)
    real = run.load_cell

    def cut(name, root=run.ROOT):
        cell = real(name, root)
        cell.traffic = dict(cell.traffic, **TINY)
        return cell

    monkeypatch.setattr(run, "load_cell", cut)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield cut
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
