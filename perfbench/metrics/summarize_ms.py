"""Host milliseconds per call after the device run: reading the results
back (``fetch``, the ``device_get``s) and turning them into rows
(``summarize``), from ``SweepResult.phases``, the program's
``fleetsim.fetch`` and ``fleetsim.summarize`` spans; mean over the
window's untraced calls.

Layer: sweep API (``fleetsim.sweep_grid``, ``metrics.summarize``).
Source: the program's own spans.  Moves ``config_ticks_per_s``.  A
program without the phase spans reports nothing.
"""

LAYER = "sweep API"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "config_ticks_per_s"


def read(run):
    calls = [c for c in run.calls if not c.traced] or run.calls
    phases = [getattr(c.sweep, "phases", None) for c in calls]
    if not all(p and "fetch" in p and "summarize" in p for p in phases):
        return None
    return 1e3 * sum(p["fetch"] + p["summarize"] for p in phases) / len(phases)
