"""The seven-policy testbed cell (``testbed.all7``) rehearsed on the CPU at
a test size that keeps its two stage policies: LÆDGE, whose coordinator
CPU collapses the rack at every load of the cell, and delayed hedging.

The sound program reads correct, and its rows collapse where the
reference's do.  A coordinator whose CPU costs nothing keeps delivering
where the reference collapses, and the ``collapse_mismatch`` reading says
so; the comparison holds only the numbers in ``compare.NUMBERS`` against a
limit, and every LÆDGE row of the cell is saturated, so that reading does
not make ``correct`` false (PERF.md, open questions).  A hedge timer that
never cancels reads not correct.
"""

import json

import jax
import pytest

import run  # noqa: E402  (perfbench is on the path via conftest)

CELL = "testbed.all7"
#: the test size: one switch policy and the two stage policies at two
#: loads, 2,048 ticks
ALL7_TINY = dict(policies=["netclone", "laedge", "hedge"],
                 loads=[0.2, 0.65], n_ticks=2048)


@pytest.fixture
def tiny_all7(tiny, monkeypatch):
    cut = run.load_cell       # the conftest's cut, which this one narrows

    def narrow(name, root=run.ROOT):
        cell = cut(name, root)
        cell.traffic = dict(cell.traffic, **ALL7_TINY)
        return cell

    monkeypatch.setattr(run, "load_cell", narrow)


def _run(capsys):
    """``(result line, readings)`` of one run of the cell."""
    jax.clear_caches()
    try:
        rc = run.main(["--workload", CELL, "--seed", "424242",
                       "--seconds", "0", "--trace", "0"], require_tpu=False)
    finally:
        jax.clear_caches()
    assert rc == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    said = next(x for x in err.splitlines()
                if x.startswith("readings, not compared: "))
    readings = {k: float(v) for k, v in (
        kv.split("=") for kv in said.split(": ", 1)[1].split())}
    return line, readings


def test_sound_all7_program_reads_correct(tiny_all7, capsys):
    line, readings = _run(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 6
    assert readings["collapse_mismatch"] == 0.0


def test_free_coordinator_cpu_reads_a_collapse_mismatch(tiny_all7, capsys,
                                                        monkeypatch):
    real = run.build_sweep

    def free_cpu(cell):
        spec, overrides = real(cell)
        return spec, dict(overrides, coord_cpu_us=0.0)

    monkeypatch.setattr(run, "build_sweep", free_cpu)
    _, readings = _run(capsys)
    assert readings["collapse_mismatch"] == 1.0


def test_hedge_that_never_cancels_reads_not_correct(tiny_all7, capsys,
                                                    monkeypatch):
    from repro.fleetsim import stages

    monkeypatch.setattr(stages, "stage_hedge_cancel",
                        lambda cfg, params, state, arr, resp: state)
    line, _ = _run(capsys)
    assert line["correct"] is False
    assert line["checks"]["clone_gap"]["value"] \
        > line["checks"]["clone_gap"]["limit"]
