"""``chip_smoke.py`` rehearsed on CPU at a tiny size.

The phase functions run in-process with interpret-mode kernels (2 policies
x 2 loads x 2,000 ticks; a 2-rack fabric for the kernel phase; a 1-device
mesh for the sharded phase), so a wrong path, argument or comparison fails
here before it costs chip time.  ``main()`` itself must refuse a CPU
platform without printing the ``ok`` verdict.
"""

import sys
from pathlib import Path

import pytest
from _cores import use_core

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402  (the repo root holds the script)

TINY = dict(policies=("baseline", "netclone"), loads=(0.2, 0.5),
            n_ticks=2_000)


@pytest.fixture(scope="module")
def paper():
    return chip_smoke.phase_paper_grid(
        **TINY, xval_policies=TINY["policies"], xval_loads=TINY["loads"],
        n_requests=2_000)


def test_paper_grid_phase_runs_staged(paper):
    assert paper.backend == "staged"
    assert paper.n_configs == 4
    assert paper.metrics.n_arrivals.shape == (4,)


def test_fused_phase_is_bit_identical_to_staged(paper, monkeypatch):
    # the phase must find the fused core selected: on the chip it is
    use_core(monkeypatch, "fused")
    sw = chip_smoke.phase_fused(paper, **TINY)
    assert sw.backend == "fused"


def test_tickfuse_phase_is_bit_identical_to_sequential_filter():
    # interpret mode: the program holds no compiled kernel on CPU
    assert chip_smoke.phase_tickfuse(racks=2, expect_custom_call=False,
                                     **TINY) is False


def test_four_chip_phase_on_one_device():
    slabs = chip_smoke.phase_four_chips(devices=1, **TINY)
    assert list(slabs.values()) == [4]


def test_main_refuses_a_cpu_platform(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "needs a TPU" in captured.err
