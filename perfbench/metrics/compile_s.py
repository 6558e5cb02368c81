"""Seconds the warm-up call spent in ``lower(...).compile()``: a compile
on a cold cache, a load from JAX's persistent cache on a warm one
(``SweepResult.compile_s``).

Layer: engine entry (``fleetsim.lower`` → ``engine._entry``).  Source:
the program's own span around lowering and compiling.  Moves ``setup_s``.
"""

LAYER = "engine entry"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    return run.warmup.compile_s
