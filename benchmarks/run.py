"""Benchmark harness: one entry per paper table/figure + system microbenches.

Prints ``name,us_per_call,derived`` CSV lines, a claims scoreboard checked
against the paper's findings, and (when dry-run artifacts exist under
results/dryrun) the roofline table.

    PYTHONPATH=src python -m benchmarks.run [figures...]
    PYTHONPATH=src python -m benchmarks.run --engine fleetsim
    PYTHONPATH=src python -m benchmarks.run --engine fleetsim --racks 4 \
        --hot-rack-weight 3.0 --straggler-mult 2.0 --out /tmp/bench.json
    PYTHONPATH=src python -m benchmarks.run --engine fleetsim \
        --devices 2 --shard --out /tmp/bench_shard.json
    REPRO_BENCH_FAST=1  → reduced request counts (CI)

``--engine fleetsim`` runs the policy × load × seed grid through the jitted,
vmapped FleetSim (one device program for the whole grid): the grid is a
declarative ``repro.scenarios.SweepSpec`` over every policy registered for
both engines, with wall-clock + simulated-MRPS numbers, per-rack tail
latencies, and the DES cross-validation scoreboard.  ``--out PATH`` writes
the artifact (by default nothing is written, keeping the checked-in
``results/bench/BENCH_fleetsim.json`` reference stable).  ``--racks N``
sweeps the 2-tier fabric (spine + N rack switches); ``--hot-rack-weight`` /
``--straggler-mult`` inject rack skew.

``--shard`` lays the grid out over every visible device
(``repro.fleetsim.shard``); ``--devices N`` splits a CPU host into N XLA
devices (``--xla_force_host_platform_device_count``, set before jax
initializes) so the multi-device program is benchmarkable anywhere;
``--hedge-delays 50,75,100`` adds the traced hedge-delay grid axis (the
delay/load plane in one program).  The artifact records the device count
and sharding layout so the perf trajectory distinguishes 1-device from
N-device runs.  Unknown figure names and ``--engine`` values are hard
argparse errors.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path


def _microbenches() -> list[str]:
    """Per-call timings of the hot-path primitives (CPU; TPU kernels run in
    interpret mode, so kernel numbers are semantics checks, not speed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import switch_jax as sw
    from repro.core.simulator import Simulator
    from repro.core.workloads import ExponentialService
    from repro.kernels.ops import fingerprint_filter

    lines = []

    def time_it(name, fn, n=20, per: int | None = None):
        fn()  # warmup / compile
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        us = (time.perf_counter() - t0) / n * 1e6
        derived = f"ns_per_item={us * 1000 / per:.0f}" if per else ""
        lines.append(f"{name},{us:.1f},{derived}")

    # vectorized dispatch tick (1024 requests per launch)
    st = sw.init_switch_state(64, 2, 4096)
    gp = sw.group_pairs_array(64)
    grp = jnp.asarray(np.random.default_rng(0).integers(0, gp.shape[0], 1024),
                      jnp.int32)
    time_it("dispatch_tick_1024", lambda: jax.block_until_ready(
        sw.dispatch_tick(st, gp, grp)[1].cloned), per=1024)
    # fingerprint filter kernel (interpret mode on CPU)
    tables = jnp.zeros((2, 4096), jnp.int32)
    rid = jnp.asarray(np.arange(1, 257), jnp.int32)
    idx = jnp.zeros(256, jnp.int32)
    clo = jnp.ones(256, jnp.int32)
    time_it("fingerprint_filter_256", lambda: jax.block_until_ready(
        fingerprint_filter(tables, rid, idx, clo)[1]), n=5)
    # DES simulator throughput
    svc = ExponentialService(25.0)
    t0 = time.perf_counter()
    Simulator("netclone", svc, seed=0).run(offered_load=0.5, n_requests=5000)
    dt = time.perf_counter() - t0
    lines.append(f"des_per_request,{dt/5000*1e6:.1f},requests_per_s="
                 f"{5000/dt:.0f}")
    return lines


def run_fleetsim(args) -> None:
    """One jitted sweep over the full policy × load × seed grid (optionally
    a multi-rack fabric with hot-rack / straggler-rack skew), plus the DES
    cross-validation scoreboard on a subset of overlapping points.

    Built on the Scenario API: the grid is a declarative ``SweepSpec`` whose
    ``policies="registered"`` default expands to every policy registered for
    both engines — a custom registration enters the benchmark with no edits
    here.  The artifact is written only when ``--out`` is given, so routine
    sweeps stop rewriting the checked-in ``BENCH_fleetsim.json``.
    """
    import os
    from dataclasses import replace

    import jax

    from repro.fleetsim.shard import ShardSpec
    from repro.fleetsim.validate import cross_validate_spec
    from repro.scenarios import Scenario, ServiceSpec, SweepSpec, registry

    fast = bool(int(os.environ.get("REPRO_BENCH_FAST", "0")))
    loads = [0.1, 0.2, 0.35, 0.5, 0.65, 0.8, 0.9, 0.95][:args.loads]
    delays = tuple(float(d) for d in args.hedge_delays.split(",")) \
        if args.hedge_delays else ()
    base = Scenario(
        name="bench", racks=args.racks, servers=args.servers,
        workers=args.workers,
        n_ticks=min(args.ticks, 10_000) if fast else args.ticks,
        hot_rack_weight=args.hot_rack_weight,
        straggler_rack_mult=args.straggler_mult,
        service=ServiceSpec.exponential(25.0))
    spec = SweepSpec(base=base, loads=tuple(loads),
                     seeds=tuple(range(args.seeds)),
                     hedge_delays=delays,
                     shard=ShardSpec() if args.shard else None)
    policies = spec.resolved_policies()

    # the delay axis only multiplies hedge-timer policies
    n_hedge = sum(registry.needs_hedge_timer(p) for p in policies)
    n_cfg = (len(policies) + n_hedge * (max(len(delays), 1) - 1)) \
        * len(loads) * args.seeds
    print(f"== fleetsim sweep: {len(policies)} policies x {len(loads)} loads "
          f"x {args.seeds} seeds"
          + (f" (x {len(delays)} hedge delays on {n_hedge} hedge "
             "policies)" if delays else "")
          + f" = {n_cfg} configurations, "
          f"{args.racks} rack(s) x {args.servers} servers, "
          f"{base.n_ticks} ticks each ==")
    if args.shard:
        print(f"== sharded over {len(jax.devices())} device(s) "
              f"(mesh axis 'grid') ==")
    sw = spec.run_fleetsim()
    cost = ""
    if sw.cost_flops is not None:
        cost = f"  {sw.cost_flops/1e9:.2f} GFLOP"
        if sw.cost_bytes is not None:
            cost += f"/{sw.cost_bytes/1e9:.2f} GB per launch"
    print(f"compile {sw.compile_s:.1f}s  run {sw.wall_clock_s:.1f}s  "
          f"total {sw.compile_s + sw.wall_clock_s:.1f}s  "
          f"{sw.simulated_requests/1e6:.1f}M simulated requests  "
          f"{sw.simulated_mrps:.2f} MRPS-simulated  "
          f"[{sw.backend} backend, {sw.n_devices} device(s), pad {sw.n_pad}]"
          + cost)

    keys = list(sw.results[0].row().keys())
    print(",".join(keys))
    for r in sw.results:
        if r.seed == 0:
            print(",".join(str(r.row()[k]) for k in keys))

    checks = []
    if not args.no_validate:
        # the DES is single-ToR, so this cross-validates the fabric's
        # n_racks=1 path — which is bit-identical to the per-rack machinery
        # every rack of a multi-rack sweep runs (tests/test_fleetsim_fabric)
        print("\n== DES cross-validation, single-rack path (documented "
              "tolerances in repro/fleetsim/validate.py) ==")
        vspec = SweepSpec(
            base=replace(base, racks=1, hot_rack_weight=1.0,
                         straggler_rack_mult=1.0),
            policies=("baseline", "netclone", "c-clone"),
            loads=(0.2, 0.5, 0.8), seeds=(0,))
        checks = cross_validate_spec(
            vspec, n_requests=8_000 if fast else 20_000)
        for c in checks:
            print(("[PASS] " if c.ok else "[FAIL] ") + c.describe())
        print(f"{sum(c.ok for c in checks)}/{len(checks)} points agree")

    if not args.out:
        print("\n(no --out given: artifact not written)")
        return
    from repro.fleetsim.sweep import rack_skew

    # record the very weights the sweep ran with (same helper the
    # SweepSpec path uses), not a hand-rebuilt copy of its convention
    weights, _ = rack_skew(base.fleet_config(), args.hot_rack_weight,
                           args.straggler_mult)
    payload = {
        "engine": "fleetsim",
        "n_racks": args.racks,
        "n_servers_per_rack": args.servers,
        "rack_weights": [float(w) for w in weights],
        "straggler_rack_mult": args.straggler_mult,
        "n_configs": sw.n_configs,
        # execution layout: staged vs fused and 1-device vmap vs N-device
        # sharded runs are not comparable rows on the perf trajectory, so
        # the artifact says which (check_perf_trend keys baselines on both)
        "backend": sw.backend,
        "n_devices": sw.n_devices,
        "shard": None if sw.shard is None
        else {**sw.shard.to_json(), "n_pad": sw.n_pad},
        "hedge_delays": list(delays),
        "n_ticks": base.n_ticks,
        # compile vs run split is ALWAYS recorded separately: compile cost
        # amortizes across runs of the same static config, run time is the
        # perf-trend metric (tools/check_perf_trend.py)
        "wall_clock_s": round(sw.wall_clock_s, 3),
        "run_s": round(sw.wall_clock_s, 3),
        "compile_s": round(sw.compile_s, 3),
        "total_s": round(sw.compile_s + sw.wall_clock_s, 3),
        # lowered-HLO cost analysis (XLA's per-launch estimate), when the
        # platform exposes one; an explicit reason rides along when it
        # doesn't, so a null is a recorded fact rather than a missing key
        "cost_analysis": {
            "flops": sw.cost_flops,
            "bytes_accessed": sw.cost_bytes,
            **({} if sw.cost_flops is not None else
               {"unavailable_reason":
                "compiled.cost_analysis() exposed no flops/bytes on this "
                "platform/jax version for the compiled sweep program"}),
        },
        "simulated_requests": sw.simulated_requests,
        "simulated_mrps": round(sw.simulated_mrps, 3),
        "sweep_spec": spec.to_json(),
        "rows": [r.row() for r in sw.results],
        "cross_validation": [
            {"policy": c.policy, "load": c.load, "pass": bool(c.ok),
             "saturated": bool(c.saturated), "detail": c.describe()}
            for c in checks],
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=1))
    print(f"\nwrote {out}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("figures", nargs="*", help="figure names (DES engine)")
    ap.add_argument("--figure", action="append", default=[],
                    metavar="NAME",
                    help="run one figure by name (repeatable; same set as "
                         "the positional form, e.g. --figure llm for the "
                         "ServeSim batch-server sweep)")
    ap.add_argument("--engine", choices=["figures", "fleetsim"],
                    default="figures")
    ap.add_argument("--ticks", type=int, default=50_000,
                    help="fleetsim ticks per configuration")
    ap.add_argument("--loads", type=int, default=8,
                    help="number of load points (fleetsim)")
    ap.add_argument("--seeds", type=int, default=5,
                    help="seeds per (policy, load) cell (fleetsim)")
    ap.add_argument("--racks", type=int, default=1,
                    help="fabric racks (fleetsim; >1 adds the spine tier)")
    ap.add_argument("--servers", type=int, default=6,
                    help="servers per rack (fleetsim)")
    ap.add_argument("--workers", type=int, default=15)
    ap.add_argument("--hot-rack-weight", type=float, default=1.0,
                    help="arrival-weight multiplier for rack 0 (fleetsim)")
    ap.add_argument("--straggler-mult", type=float, default=1.0,
                    help="execution slowdown for the last rack (fleetsim)")
    ap.add_argument("--devices", type=int, default=0,
                    help="split a CPU host into N XLA devices "
                         "(--xla_force_host_platform_device_count; must be "
                         "set before jax initializes, which this does)")
    ap.add_argument("--shard", action="store_true",
                    help="shard the fleetsim sweep grid over every visible "
                         "device (repro.fleetsim.shard); without it the "
                         "grid vmaps onto one device")
    ap.add_argument("--hedge-delays", default="",
                    help="comma-separated hedge delays (µs) added as a "
                         "traced grid axis, e.g. 50,75,100 (fleetsim)")
    ap.add_argument("--no-validate", action="store_true",
                    help="skip the DES cross-validation pass")
    ap.add_argument("--out", default=None,
                    help="write the fleetsim sweep artifact to this path "
                         "(default: none, so routine runs don't rewrite the "
                         "checked-in results/bench/BENCH_fleetsim.json)")
    args = ap.parse_args()

    if args.devices:
        # must land in the environment before jax creates its backend (all
        # jax imports in this module are deliberately function-local)
        import os

        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS", ""),
            f"--xla_force_host_platform_device_count={args.devices}"]))

    from repro import compile_cache

    compile_cache.enable()
    if args.engine == "fleetsim":
        run_fleetsim(args)
        return

    from benchmarks.figures import ALL_FIGURES

    wanted = (args.figures + args.figure) or list(ALL_FIGURES)
    unknown = [n for n in wanted if n not in ALL_FIGURES]
    if unknown:
        ap.error(f"unknown figure(s) {unknown}; have {list(ALL_FIGURES)}")
    outdir = Path("results/bench")
    outdir.mkdir(parents=True, exist_ok=True)

    print("== microbenches (name,us_per_call,derived) ==")
    for line in _microbenches():
        print(line)

    all_rows, all_claims = [], []
    timing: dict[str, float] = {}
    for name in wanted:
        t0 = time.time()
        rows, claims = ALL_FIGURES[name]()
        timing[name] = round(time.time() - t0, 3)
        all_rows += rows
        all_claims += claims
        print(f"\n== {name} ({timing[name]:.1f}s) ==")
        if rows:
            keys = list(rows[0].keys())
            print(",".join(keys))
            for r in rows:
                print(",".join(str(r.get(k, "")) for k in keys))

    print("\n== paper-claims scoreboard ==")
    n_pass = 0
    for cid, desc, ok, detail in all_claims:
        n_pass += ok
        print(f"[{'PASS' if ok else 'FAIL'}] {cid}: {desc} — {detail}")
    print(f"{n_pass}/{len(all_claims)} claims validated")

    (outdir / "rows.json").write_text(json.dumps(all_rows, indent=1))
    (outdir / "claims.json").write_text(json.dumps(
        [{"id": c, "desc": d, "pass": bool(p), "detail": x}
         for c, d, p, x in all_claims], indent=1))
    (outdir / "timing.json").write_text(json.dumps(
        {"figures": timing, "total_s": round(sum(timing.values()), 3)},
        indent=1))

    # roofline table, if the dry-run has produced artifacts
    if list(Path("results/dryrun").glob("*__sp.json")):
        from repro.analysis import roofline
        rows = roofline.table()
        if rows:
            print("\n== roofline (single-pod 16x16, v5e) ==")
            print(roofline.format_table(rows))


if __name__ == "__main__":
    main()
