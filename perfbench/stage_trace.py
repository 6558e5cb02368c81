"""Device time per tick stage: one sweep call of a cell, profiled, reduced
to the self time of each scope the program names.

    python3 perfbench/stage_trace.py --workload testbed.switch5 --seed 7 \\
        --out stages.json

The program runs each tick stage under a ``jax.named_scope``
(``tick.arrival`` … ``tick.client``, ``build_step``) and its set-up under
``fleetsim.init``, ``fleetsim.draw`` and ``fleetsim.pack``; every op
compiled from a stage carries the scope in its ``op_name`` metadata.  A
fusion takes its root's scope: XLA gives the fusion its root op's
metadata, so a fusion that spans two stages counts wholly to the stage
of its last op.  Host phases are the program's ``fleetsim.<phase>`` spans
(``repro.fleetsim.spans``).

The run sets up as ``run.py`` does (one warm-up call, which also yields
the compiled program's text), but compiles afresh: JAX's persistent cache
keys a program without its ``op_name`` metadata, so a program loaded from
it may carry the names of the build that filled it.  Then it profiles
one whole call and prints a table: every scope's self time per grid
tick, the ``while`` loops' own time (in-scan time in which no body op
runs), the unscoped time, and their sum beside the busiest chip's busy
time per tick (what the ``tick_device_us`` metric reads); then the top ops
of each scope and the chip's idle time under each ``fleetsim.*`` phase.

:func:`reduce_stages` works on plain lists, so the reduction is checked on
synthetic traces (``tests/benchmark/test_bench_stages.py``).

* self time of an op: its duration inside the window less the part its
  nested ops cover (a ``while`` event spans its whole loop, its body's
  ops run inside it);
* scope of an op: the innermost ``tick.*``/``fleetsim.*`` component of its
  ``op_name``, read from the event's ``tf_op`` stat where the trace carries
  it, else from the compiled program's text by the op's HLO name;
* an op with no scope is loop time if it is a ``while``, else unscoped.
  On the v5e, ops the compiler makes itself carry no ``op_name``: the
  fusions of a batched scatter (the filter tables', the queue rings') and
  the layout copies feeding them.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import trace_reduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: profiler output of the profiled call (beside run.py's own, never in it)
TRACE_DIR = ROOT / ".stage_trace"
SCOPE = re.compile(r"\b(?:tick|fleetsim)\.[a-z_]+")
METADATA = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')
LOOP = "loop"
UNSCOPED = "unscoped"

#: (HLO text of the op, start_s, end_s, scope)
ScopedOp = tuple[str, float, float, str]


def scope_of(op_name: str) -> str:
    """The innermost ``tick.*``/``fleetsim.*`` component of an ``op_name``
    (``jit(run)/vmap(fleetsim.draw)/…`` → ``fleetsim.draw``), or ``""``."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else ""


def hlo_name(text: str) -> str:
    """``%fusion.617 = f32[8]{0} fusion(..)`` → ``fusion.617``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def is_loop(text: str) -> bool:
    return hlo_name(text).split(".", 1)[0] == "while" or " while(" in text


def op_names(hlo_text: str) -> dict[str, str]:
    """HLO instruction name → its ``op_name`` metadata, from a compiled
    program's text (``compiled.as_text()``)."""
    out = {}
    for line in hlo_text.splitlines():
        m = METADATA.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def resolve_scope(text: str, stats: dict, names: dict[str, str]) -> str:
    """An op's scope: the event's ``tf_op`` stat first, else the program's
    metadata by the op's HLO name."""
    tf_op = stats.get("tf_op")
    if tf_op:
        return scope_of(str(tf_op))
    return scope_of(names.get(hlo_name(text), ""))


@dataclass
class Stages:
    """Self time on the busiest device, split by scope."""

    device: str
    busy_s: float
    stage_s: dict[str, float]          # scope → self seconds
    loop_self_s: float                 # unscoped while ops' own time
    unscoped_s: float
    top_ops: dict[str, list[tuple[str, float]]] = field(default_factory=dict)
    idle_by_phase: dict[str, float] = field(default_factory=dict)

    @property
    def total_s(self) -> float:
        return sum(self.stage_s.values()) + self.loop_self_s + self.unscoped_s


def self_times(ops: list[ScopedOp], win: tuple[float, float]
               ) -> list[tuple[ScopedOp, float]]:
    """Each op with its self time inside ``win``: its clipped duration less
    the clipped durations of the ops nested directly inside it."""
    lo, hi = win
    clipped = sorted(((o, max(o[1], lo), min(o[2], hi)) for o in ops
                      if min(o[2], hi) > max(o[1], lo)),
                     key=lambda x: (x[1], -x[2]))
    own = [e - s for _, s, e in clipped]
    stack: list[int] = []
    for i, (_, s, e) in enumerate(clipped):
        while stack and clipped[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(o, t) for (o, _, _), t in zip(clipped, own)]


def reduce_stages(device_ops: dict[str, list[ScopedOp]],
                  host_spans: list[tuple[str, float, float]],
                  win: tuple[float, float], top: int = 5) -> Stages | None:
    """Self time per scope on the busiest device inside ``win``, and the
    device's idle time under each ``fleetsim.*`` host span."""
    busy_iv = {d: trace_reduce.union([(s, e) for _, s, e, _ in ops], win)
               for d, ops in device_ops.items()}
    busy = {d: sum(e - s for s, e in iv) for d, iv in busy_iv.items()}
    if not any(busy.values()):
        return None
    dev = max(busy, key=busy.get)
    stage_s: dict[str, float] = {}
    per_op: dict[str, dict[str, float]] = {}
    loop_s = unscoped_s = 0.0
    for op, t in self_times(device_ops[dev], win):
        text, _, _, scope = op
        key = scope or (LOOP if is_loop(text) else UNSCOPED)
        if scope:
            stage_s[scope] = stage_s.get(scope, 0.0) + t
        elif key == LOOP:
            loop_s += t
        else:
            unscoped_s += t
        name = trace_reduce.op_name(text)
        ops = per_op.setdefault(key, {})
        ops[name] = ops.get(name, 0.0) + t
    idle: dict[str, float] = {}
    for name, s, e in host_spans:
        s, e = max(s, win[0]), min(e, win[1])
        if not name.startswith("fleetsim.") or e <= s:
            continue
        key = name[len("fleetsim."):]
        idle[key] = idle.get(key, 0.0) + (e - s) - trace_reduce.overlap(
            busy_iv[dev], s, e)
    by_time = lambda kv: -kv[1]  # noqa: E731
    return Stages(
        device=dev, busy_s=busy[dev],
        stage_s=dict(sorted(stage_s.items(), key=by_time)),
        loop_self_s=loop_s, unscoped_s=unscoped_s,
        top_ops={k: sorted(v.items(), key=by_time)[:top]
                 for k, v in per_op.items()},
        idle_by_phase=dict(sorted(idle.items(), key=by_time)))


# ------------------------------------------------------------ the trace ---
def load(log_dir: str, names: dict[str, str]):
    """Read the newest ``.xplane.pb`` under ``log_dir``: chip ops with their
    scope, and the ``bench.*``/``fleetsim.*`` host spans."""
    from jax._src.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device_ops: dict[str, list[ScopedOp]] = {}
    spans: list[tuple[str, float, float]] = []
    for plane in pd.planes:
        if trace_reduce.CHIP_PLANE.match(plane.name):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            evs = device_ops.setdefault(plane.name, [])
            for ln in ops:
                for ev in ln.events:
                    evs.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                resolve_scope(ev.name, dict(ev.stats),
                                              names)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(("bench.", "fleetsim.")):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    return device_ops, spans


class _Keep:
    """``sweep.lower``'s result, keeping the compiled program's text."""

    def __init__(self, lowered, texts):
        self._l, self._texts = lowered, texts

    def compile(self, *a, **kw):
        compiled = self._l.compile(*a, **kw)
        self._texts.append(compiled.as_text())
        return compiled


def profile(workload: str, seed: int):
    """Set up as ``run.py`` does, then profile one whole call.  Returns
    ``(stages, n_ticks, n_scoped, window_s)``, ``n_scoped`` the
    compiled program's ops whose metadata names a scope."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import run
    from repro.fleetsim import sweep as sweep_mod

    cell = run.load_cell(workload)
    # compile afresh: the persistent cache's key leaves out op_name
    # metadata, so a program loaded from it may carry another build's
    # names (JAX decides on the cache once per process, hence the reset)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    spec, overrides = run.build_sweep(cell)
    n = cell.traffic["seeds_per_call"]
    texts: list[str] = []
    real = sweep_mod.lower
    sweep_mod.lower = lambda *a, **kw: _Keep(real(*a, **kw), texts)
    try:
        warm = run.run_call(spec, overrides, run.call_seeds(seed, 0, 0, n))
    finally:
        sweep_mod.lower = real
    print(f"stages: warm-up call {warm.wall_s:.3f} s, compile events "
          f"{getattr(warm.sweep, 'compile_events', None)}", file=sys.stderr)
    names = op_names(texts[-1]) if texts else {}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            call = run.run_call(spec, overrides,
                                run.call_seeds(seed, 1, 0, n))
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    device_ops, spans = load(str(TRACE_DIR), names)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    win = trace_reduce.Trace(device_ops={}, host_spans=spans).window()
    stages = reduce_stages(device_ops, spans, win) if win else None
    print(f"stages: trace read and reduced in {time.perf_counter() - t0:.3f}"
          f" s; call device {call.device_s:.3f} s", file=sys.stderr)
    n_scoped = sum(1 for v in names.values() if scope_of(v))
    return stages, cell.n_ticks, n_scoped, (win[1] - win[0]) if win else 0.0


def table(st: Stages, n_ticks: int) -> list[str]:
    us = 1e6 / n_ticks
    rows = [*st.stage_s.items(), ("loop (while self)", st.loop_self_s),
            ("unscoped", st.unscoped_s)]
    out = [f"{'scope':<20} {'self s':>10} {'us/tick':>10} {'share':>7}"]
    for k, v in rows:
        out.append(f"{k:<20} {v:>10.6f} {v * us:>10.3f} "
                   f"{100 * v / st.busy_s:>6.2f}%")
    out.append(f"{'sum':<20} {st.total_s:>10.6f} {st.total_s * us:>10.3f}"
               f"   busy {st.busy_s * us:.3f} us/tick ({st.device})")
    for k, ops in st.top_ops.items():
        out.append(f"top ops of {k}: " + "; ".join(
            f"{n} {v:.4f} s" for n, v in ops))
    out.append("idle by fleetsim phase: " + " ".join(
        f"{k}={v * 1e3:.3f} ms" for k, v in st.idle_by_phase.items()))
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None,
                    help="also write the reduction as JSON here")
    args = ap.parse_args(argv)
    stages, n_ticks, n_scoped, window_s = profile(args.workload, args.seed)
    print(f"stages: {n_scoped} ops of the compiled program name a scope",
          file=sys.stderr)
    if stages is None:
        print("stages: the trace holds no chip op", file=sys.stderr)
        return 1
    print("\n".join(table(stages, n_ticks)))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"workload": args.workload, "seed": args.seed,
             "n_ticks": n_ticks, "window_s": window_s,
             "scoped_ops": n_scoped, "busy_s": stages.busy_s,
             "device": stages.device, "stage_s": stages.stage_s,
             "loop_self_s": stages.loop_self_s,
             "unscoped_s": stages.unscoped_s,
             "top_ops": stages.top_ops,
             "idle_by_phase": stages.idle_by_phase}, indent=1))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(ROOT / "src"))
    raise SystemExit(main())
