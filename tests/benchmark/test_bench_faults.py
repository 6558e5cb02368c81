"""The chip benchmark's ``correct`` against a broken program: each run
skips the look for a chip and drives the rest of a run — set-up, window,
comparison — at a tiny size, with the timed path broken underneath, and
``correct`` must read false.  The faults are those a grid of simulations
can have: a tick that returns its state unchanged, half of the grid's rows
never simulated (their answers copied from the other half), and an answer
altered where it is produced (the switch filter never drops, the switch
never clones, the servers run every request for half its time).  The cell
runs on one chip, so there is no exchange between chips to leave out.  The
same run with nothing broken must read true."""

import json

import jax
import jax.numpy as jnp
import pytest

import run  # noqa: E402  (perfbench is on the path via conftest)

CELL = "testbed.switch5"


def _state_unchanged(monkeypatch):
    from repro.fleetsim import engine, fused

    def frozen(cfg, params, group_pairs):
        return lambda state, xs: (state, None)

    monkeypatch.setattr(engine, "build_step", frozen)
    monkeypatch.setattr(fused, "build_step", frozen)


def _half_batch(monkeypatch):
    from repro.fleetsim import sweep

    real = sweep.lower

    class Half:
        def __init__(self, compiled):
            self._c = compiled

        def __call__(self, params):
            m = self._c(params)
            n = jax.tree.leaves(m)[0].shape[0]
            half = n // 2
            # the second half of the rows is never simulated: its answers
            # are the first half's
            return jax.tree.map(
                lambda a: jnp.concatenate([a[:n - half], a[:half]]), m)

        def __getattr__(self, name):
            return getattr(self._c, name)

    class Lowered:
        def __init__(self, lowered):
            self._l = lowered

        def compile(self):
            return Half(self._l.compile())

    monkeypatch.setattr(sweep, "lower", lambda *a, **k: Lowered(real(*a,
                                                                    **k)))


def _answer_altered(monkeypatch):
    from repro.fleetsim import stages

    real = stages._filter_responses

    def never_drop(*a, **k):
        sstate, tables, drop = real(*a, **k)
        return sstate, tables, jnp.zeros_like(drop)

    monkeypatch.setattr(stages, "_filter_responses", never_drop)


def _clone_altered(monkeypatch):
    from repro.fleetsim import stages

    real = stages.route_fabric

    def never_clone(*a, **k):
        dst1, dst2, cloned, clo1, clo2 = real(*a, **k)
        return (dst1, dst2, jnp.zeros_like(cloned), jnp.zeros_like(clo1),
                clo2)

    monkeypatch.setattr(stages, "route_fabric", never_clone)


def _runtime_altered(monkeypatch):
    from repro.fleetsim import stages

    real = stages._execute
    monkeypatch.setattr(stages, "_execute",
                        lambda *a, **k: 0.5 * real(*a, **k))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered,
          "clone_altered": _clone_altered,
          "runtime_altered": _runtime_altered}


def _correct(name, capsys) -> bool:
    jax.clear_caches()
    try:
        rc = run.main(["--workload", name, "--seed", "424242",
                       "--seconds", "0", "--trace", "0"], require_tpu=False)
    finally:
        jax.clear_caches()
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])[
        "correct"]


def test_sound_program_reads_correct(tiny, capsys):
    assert _correct(CELL, capsys) is True


@pytest.mark.parametrize("fault", list(FAULTS))
def test_broken_program_reads_not_correct(tiny, capsys, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    assert _correct(CELL, capsys) is False
