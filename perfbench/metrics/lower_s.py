"""Seconds the warm-up call spent in its ``lower`` phase: tracing the
sweep program to a jaxpr and lowering it to MLIR, before the compile or
the persistent cache answers (``SweepResult.phases["lower"]``, the
program's ``fleetsim.lower`` span).

Layer: engine entry (``fleetsim.lower`` → ``engine._entry``).  Source: the
program's own span.  Moves ``setup_s``.  A program without the phase
spans reports nothing.
"""

LAYER = "engine entry"
UNIT = "s"
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    phases = getattr(run.warmup.sweep, "phases", None)
    if not phases or "lower" not in phases:
        return None
    return phases["lower"]
