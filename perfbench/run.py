"""FleetSim's chip benchmark: one run of one cell.

    python3 perfbench/run.py --workload testbed.switch5 --seed 7 --seconds 10 \\
        --trace 0

A cell (``BENCHMARK.json`` → ``workloads``) names a configuration
(``configs/<name>.json``: the simulated testbed) and a traffic mix
(``traffic/<name>.json``: the policy × load × seed grid of one call, its
horizon, service and the comparison's limits).  The run

1. refuses anything but a TPU with as many chips as the cell asks for;
2. sets up: builds the cell's ``SweepSpec``, turns on the persistent
   compilation cache and makes one warm-up call, which compiles the
   program or reads it from the cache (``setup_s`` ends here);
3. measures: repeats the user's entry, ``SweepSpec.run_fleetsim()``, one
   whole grid per call with new seeds drawn from ``--seed``, until the
   first call that ends after ``--seconds``; ``config_ticks_per_s`` is grid
   rows × ticks of every call over the window's wall time;
4. with ``--trace 1`` traces one call of the window with the JAX profiler,
   host phases marked, and reports the per-layer metrics
   (``metrics/<name>.py``) instead of the end-to-end ones;
5. checks the answers: every row of one call, drawn from the seed, against
   the plain reference (``reference/des.py``) run on the host, each
   compared number beside its limit (``compare.py``).

The last line of standard output is the result as one JSON object.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, replace  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: profiler output of the traced run (a fixed path inside the checkout)
TRACE_DIR = ROOT / ".bench_trace"
_MASK64 = (1 << 64) - 1


def _process_age_s() -> float:
    """Seconds since this process started (the interpreter's own start-up
    included), where Linux's ``/proc`` tells it; 0 elsewhere."""
    try:
        with open("/proc/self/stat") as f:
            start = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return max(0.0, up - start / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE_AT_T0 = _process_age_s()


def since_start() -> float:
    return _AGE_AT_T0 + time.monotonic() - _T0


class Refused(Exception):
    """The run cannot be made here (no TPU, too few chips, unknown cell)."""


# ------------------------------------------------------------------ cell ---
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def n_ticks(self) -> int:
        return int(self.traffic["n_ticks"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Find a cell, its configuration and its traffic by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads(
            (HERE / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def call_seeds(seed: int, stream: int, k: int, n: int) -> tuple[int, ...]:
    """``n`` simulation seeds for call ``k`` of a stream, drawn from the
    run's seed; each fits the program's int32 seed lane."""
    import numpy as np

    ss = np.random.SeedSequence([seed & _MASK64, stream, k])
    return tuple(int(x) % (2 ** 31 - 1) for x in ss.generate_state(n))


def build_sweep(cell: Cell):
    """The cell's ``SweepSpec`` (one seed placeholder per grid seed) and
    the ``FleetConfig`` overrides its configuration states."""
    from repro.fleetsim import ShardSpec
    from repro.scenarios import Scenario, ServiceSpec, SweepSpec

    c, t = cell.config, cell.traffic
    svc = t["service"]
    service = getattr(ServiceSpec, svc["kind"])(
        *svc["params"], jitter_p=svc["jitter_p"],
        jitter_mult=svc["jitter_mult"])
    base = Scenario(name=cell.name, racks=c["racks"],
                    servers=c["servers_per_rack"],
                    workers=c["workers_per_server"], n_ticks=cell.n_ticks,
                    service=service, dt_us=c["dt_us"])
    shard = t.get("shard_devices")
    spec = SweepSpec(base=base, policies=tuple(t["policies"]),
                     loads=tuple(t["loads"]),
                     seeds=tuple(range(t["seeds_per_call"])),
                     shard=ShardSpec(devices=shard) if shard else None)
    costs = c["costs_us"]
    overrides = dict(n_clients=c["clients"],
                     n_filter_tables=c["filter_tables"],
                     n_filter_slots=c["filter_slots"],
                     link_us=costs["link"],
                     server_overhead_us=costs["server_overhead"],
                     client_rx_us=costs["client_rx"],
                     client_tx_us=costs["client_tx"],
                     pipeline_pass_us=costs["pipeline_pass"],
                     coord_cpu_us=costs["coord_cpu"])
    return spec, overrides


# ---------------------------------------------------------------- window ---
@dataclass
class Call:
    """One whole grid call of the window."""

    seeds: tuple[int, ...]
    wall_s: float          # host wall time of run_fleetsim()
    device_s: float        # its SweepResult.wall_clock_s
    compile_s: float       # its SweepResult.compile_s
    sweep: object          # the SweepResult
    traced: bool = False


class Phases:
    """Host phase spans (``bench.<phase>``) of a traced call, opened and
    closed where the program's own calls begin and end."""

    def __init__(self):
        self._cur = None

    def switch(self, name: str | None) -> None:
        import jax

        if self._cur is not None:
            self._cur.__exit__(None, None, None)
        self._cur = None
        if name is not None:
            self._cur = jax.profiler.TraceAnnotation("bench." + name)
            self._cur.__enter__()


class _Compiled:
    def __init__(self, compiled, phases):
        self._c, self._p = compiled, phases

    def __call__(self, *args):
        import jax

        self._p.switch("device")
        out = jax.block_until_ready(self._c(*args))
        self._p.switch("summarize")
        return out

    def __getattr__(self, name):
        return getattr(self._c, name)


class _Lowered:
    def __init__(self, lowered, phases):
        self._l, self._p = lowered, phases

    def compile(self, *a, **kw):
        self._p.switch("compile")
        return _Compiled(self._l.compile(*a, **kw), self._p)


def _marked(fn, phases):
    def lower(*a, **kw):
        phases.switch("lower")
        return _Lowered(fn(*a, **kw), phases)
    return lower


def run_call(spec, overrides, seeds, phases: Phases | None = None) -> Call:
    """One whole grid call through the user's entry.  With ``phases`` the
    program's lowering, compile and device calls are marked as host spans
    for the profiler (the answers are the same)."""
    from repro.fleetsim import sweep as sweep_mod

    saved = (sweep_mod.lower, sweep_mod.lower_sharded)
    if phases is not None:
        sweep_mod.lower = _marked(saved[0], phases)
        sweep_mod.lower_sharded = _marked(saved[1], phases)
        phases.switch("params")
    try:
        t0 = time.perf_counter()
        sw = replace(spec, seeds=seeds).run_fleetsim(**overrides)
        wall = time.perf_counter() - t0
    finally:
        if phases is not None:
            phases.switch(None)
            sweep_mod.lower, sweep_mod.lower_sharded = saved
    return Call(seeds=seeds, wall_s=wall, device_s=sw.wall_clock_s,
                compile_s=sw.compile_s, sweep=sw, traced=phases is not None)


def measure(cell: Cell, spec, overrides, seed: int, seconds: float,
            trace: bool):
    """The window: whole calls until the first that ends after
    ``seconds``.  With ``trace`` the first call is profiled."""
    import jax

    n = cell.traffic["seeds_per_call"]
    calls: list[Call] = []
    reduced = None
    t0 = time.perf_counter()
    while True:
        k = len(calls)
        seeds = call_seeds(seed, 1, k, n)
        if trace and k == 0:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # host spans come from TraceAnnotation alone: no Python
            # function tracer, no HLO protos in the trace
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.window"):
                    calls.append(run_call(spec, overrides, seeds, Phases()))
            finally:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                t_stop = time.perf_counter() - t_stop
        else:
            calls.append(run_call(spec, overrides, seeds))
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if trace:
        import trace_reduce

        t0 = time.perf_counter()
        loaded = trace_reduce.load(str(TRACE_DIR))
        t1 = time.perf_counter()
        reduced = trace_reduce.reduce(loaded)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        print(f"trace: stop {t_stop:.3f} s, load {t1 - t0:.3f} s, reduce "
              f"{time.perf_counter() - t1:.3f} s", file=sys.stderr)
    return calls, window_s, reduced


# ------------------------------------------------------------ correctness ---
def row_failed(m: dict) -> bool:
    """A row with no usable result: clipped arrival or response lanes, or
    no latency recorded."""
    import numpy as np

    return (int(m["n_truncated"]) > 0 or int(m["n_resp_clipped"]) > 0
            or int(np.asarray(m["hist"]).sum()) == 0)


def row_metrics(sweep, i: int) -> dict:
    import numpy as np

    return {f: np.asarray(getattr(sweep.metrics, f))[i]
            for f in sweep.metrics._fields}


def judge(cell: Cell, rows: list[tuple[str, float]], progs, refs,
          hist: tuple[float, float, int]):
    """Hold a call's rows — ``(policy, load)`` with the program's and the
    reference's statistics — against the cell's limits.  Returns ``(ok,
    [(number, value, limit)], {reading: value})``."""
    import compare

    numbers = compare.call_numbers(rows, progs, refs, hist)
    ok, lines = compare.verdict(numbers, cell.traffic["check"]["limits"])
    return ok, lines, {k: numbers[k] for k in compare.READINGS
                       if k in numbers}


def hist_layout(spec, overrides) -> tuple[float, float, int]:
    """The program's latency histogram: lowest edge (µs), growth, bins."""
    cfg = spec.base.fleet_config(**overrides)
    return cfg.hist_lo_us, cfg.hist_growth, cfg.hist_bins


def check(cell: Cell, spec, overrides, calls: list[Call], seed: int):
    """Every row of one call, drawn from the seed, against the plain
    reference run on the host."""
    import numpy as np

    import compare
    from reference import pool

    cfg = spec.base.fleet_config(**overrides)
    hist = hist_layout(spec, overrides)
    window_us = cfg.duration_us - cfg.warmup_us
    pick = np.random.default_rng([seed & _MASK64, 2]).integers(len(calls))
    sw = calls[int(pick)].sweep
    progs = [compare.program_stats(row_metrics(sw, i), window_us=window_us,
                                   rate_per_us=r.offered_rate_mrps)
             for i, r in enumerate(sw.results)]
    refs = pool.run([pool.task(cell.config, cell.traffic, policy=r.policy,
                               load=r.offered_load, seed=r.seed,
                               rate_per_us=r.offered_rate_mrps, hist=hist)
                     for r in sw.results])
    return judge(cell, [(r.policy, r.offered_load) for r in sw.results],
                 progs, refs, hist)


# ---------------------------------------------------------------- result ---
def load_reader(name: str):
    """The per-layer metric ``name``'s reader, ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class RunRecord:
    """What the metric readers read."""

    cell: Cell
    calls: list[Call]
    warmup: Call
    window_s: float
    setup_s: float
    trace: object | None    # trace_reduce.Reduced


def end_to_end(rec: RunRecord) -> dict[str, float]:
    ticks = sum(len(c.sweep.results) * rec.cell.n_ticks for c in rec.calls)
    known = {"config_ticks_per_s": ticks / rec.window_s,
             "setup_s": rec.setup_s}
    out = {}
    for m in rec.cell.end_to_end:
        if m["name"] not in known:
            raise Refused(f"no host-clock reading for {m['name']!r}")
        out[m["name"]] = {"value": known[m["name"]], "unit": m["unit"]}
    return out


def per_layer(rec: RunRecord) -> dict[str, float]:
    out = {}
    for m in rec.cell.per_layer:
        v = load_reader(m["name"]).read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def device_info(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def main(argv: list[str] | None = None, *, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        import jax

        devices = jax.devices()
        if require_tpu and devices[0].platform != "tpu":
            raise Refused(f"needs a TPU; JAX found {devices[0].platform!r}")
        if len(devices) < cell.chips:
            raise Refused(f"{cell.name} needs {cell.chips} chips; JAX "
                          f"found {len(devices)}")
    except Refused as e:
        print(f"perfbench: {e}; no run made", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec, overrides = build_sweep(cell)
    n = cell.traffic["seeds_per_call"]
    t_start = since_start()
    warmup = run_call(spec, overrides, call_seeds(args.seed, 0, 0, n))
    setup_s = since_start()
    print(f"setup: start-up and imports {t_start:.3f} s, warm-up call "
          f"{warmup.wall_s:.3f} s (lower and compile {warmup.compile_s:.3f}"
          f" s, device {warmup.device_s:.3f} s)", file=sys.stderr)

    calls, window_s, reduced = measure(cell, spec, overrides, args.seed,
                                       args.seconds, bool(args.trace))
    print(f"window: {len(calls)} calls in {window_s:.3f} s; per call wall "
          + " ".join(f"{c.wall_s:.3f}" for c in calls) + " s, device "
          + " ".join(f"{c.device_s:.3f}" for c in calls) + " s",
          file=sys.stderr)
    dev = device_info(devices, cell.chips)
    failed = sum(row_failed(row_metrics(c.sweep, i))
                 for c in calls for i in range(len(c.sweep.results)))
    attempted = sum(len(c.sweep.results) for c in calls)
    rec = RunRecord(cell=cell, calls=calls, warmup=warmup,
                    window_s=window_s, setup_s=setup_s, trace=reduced)
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if args.trace:
        result["metrics"] = per_layer(rec)
        if reduced is not None:
            dev["busy_s"] = reduced.mean_busy_s
            dev["window_s"] = reduced.window_s
            result["breakdown"] = {
                "device_ops": [list(x) for x in reduced.device_ops],
                "idle_gaps": [list(x) for x in reduced.idle_gaps]}
    else:
        result["metrics"] = end_to_end(rec)
    result["device"] = dev

    ok, lines, readings = check(cell, spec, overrides, calls, args.seed)
    result["correct"] = bool(ok and failed == 0)
    lines.append(("failed_rows", failed, 0))
    result["checks"] = {k: {"value": _num(v), "limit": lim}
                        for k, v, lim in lines}
    print("readings, not compared: " + " ".join(
        f"{k}={v!r}" for k, v in readings.items()), file=sys.stderr)
    for k, v, lim in lines:
        print(f"check {k} = {v!r} (limit {lim!r})"
              f"{'' if v <= lim else '  FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


def _num(v):
    """A compared value for the JSON line: a non-finite gap (a row with
    no latency, say) is written as text, which JSON can hold."""
    return v if math.isfinite(v) else str(v)


if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    # JAX's persistent cache lives inside the checkout, at a fixed path,
    # whatever cache the machine names: read at JAX's import, and taken by
    # the program's own compile_cache.enable()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    raise SystemExit(main())
