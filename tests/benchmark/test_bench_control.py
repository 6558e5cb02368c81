"""The controls of the chip benchmark's comparison, kept at a test size:
the plain reference with one stated guarantee broken, put in the program's
place, must read not correct on every seed — and a second, independent
sample of the sound reference in the same place must read correct, so
the limits are not failed by any run at all."""

import pytest

import control  # noqa: E402  (perfbench is on the path via conftest)
from reference import des  # noqa: E402

CELL = "testbed.switch5"


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", des.CONTROLS)
def test_control_reads_not_correct(tiny, name, seed):
    ok, lines, _ = control.control_verdict(tiny(CELL), seed, name)
    assert not ok
    assert any(v > lim for _, v, lim in lines)


@pytest.mark.parametrize("seed", [5, 6])
def test_independent_sound_sample_reads_correct(tiny, seed):
    ok, lines, _ = control.control_verdict(tiny(CELL), seed, None)
    assert ok, lines
