"""Chip smoke test: FleetSim's sweep path, end to end, on a TPU.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # a four-chip host: the sharded grid

Every phase runs through the entry points a user calls (``SweepSpec`` /
``sweep_grid``, ``simulate`` and its AOT twin ``lower``) at the paper
testbed's size — 6 servers x 15 workers per rack, exponential 25 us
service, 1 us ticks, 50,000 ticks, one seed:

(a) the paper grid — every registered two-engine policy x the 8
    benchmark loads, which the LÆDGE and hedge policies' optional stages
    put on the staged core (``engine.select_core``) — and the plain
    reference: the DES
    (``validate.cross_validate_spec``) at baseline / netclone / c-clone x
    0.2 / 0.5 / 0.8, every point within the tolerances of
    ``repro/fleetsim/validate.py``;
(b) the non-stage policies of the same grid, for which the engine must
    select the fused core on the chip, with Metrics bit-identical to (a)'s
    staged rows;
(c) a 4-rack fabric (hot rack x3, straggler rack x2) on the core the
    engine selects (fused on the chip) with the TickFuse switch kernel,
    bit-identical to the exact
    lane-sequential filter (``filter_backend="scan"``), and the kernel's
    program must hold a compiled ``tpu_custom_call`` (not an interpreted
    kernel).  The default ``vectorized`` filter runs too and its differing
    fields are printed: it is exact except for two different requests
    colliding on one filter slot within one tick
    (``core.switch_jax.filter_tick_vectorized``), which a 50,000-tick
    4-rack run does hit;
(4) with ``--four-chips`` only: the paper grid at 5 loads (35 configs,
    padded to 36) sharded over four chips, bit-exact against the unsharded
    vmap on one chip (``validate.shard_equivalence``), with the per-device
    slabs printed.

The script refuses to run anywhere but a TPU, and any failed phase exits
non-zero.  Compile and run seconds it prints are smoke timings of a single
run, not benchmark numbers.  The last line of its output is the JSON
verdict ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: the load points of ``benchmarks/run.py --engine fleetsim``
PAPER_LOADS = (0.1, 0.2, 0.35, 0.5, 0.65, 0.8, 0.9, 0.95)
XVAL_POLICIES = ("baseline", "netclone", "c-clone")
XVAL_LOADS = (0.2, 0.5, 0.8)
FABRIC_LOADS = (0.2, 0.35, 0.5)
N_TICKS = 50_000


class PhaseFailed(RuntimeError):
    """A phase's output disagreed with what it is compared against."""


def _report(phase: str, **fields) -> None:
    print(f"[{phase}] " + "  ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _base(racks: int = 1, n_ticks: int = N_TICKS, **kw):
    from repro.scenarios import Scenario, ServiceSpec

    return Scenario(name="chip_smoke", racks=racks, servers=6, workers=15,
                    n_ticks=n_ticks, service=ServiceSpec.exponential(25.0),
                    **kw)


def non_stage_policies() -> tuple[str, ...]:
    """The registered two-engine policies that need no optional stage —
    the fused core's policy matrix."""
    from repro.scenarios import registry

    return tuple(p for p in registry.two_engine_names()
                 if not (registry.needs_coordinator(p)
                         or registry.needs_hedge_timer(p)))


def _mismatched(a, b) -> list[str]:
    """Names of the Metrics fields whose arrays differ in any bit."""
    import numpy as np

    return [f for f in a._fields
            if not np.array_equal(np.asarray(getattr(a, f)),
                                  np.asarray(getattr(b, f)))]


def _row(metrics, i):
    import jax

    return jax.tree.map(lambda x: x[i], metrics)


def _stack_params(cfg, cells):
    """The batched RunParams of ``cells`` (Scenarios sharing ``cfg``)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda *a: jnp.stack(a),
                        *[sc.run_params(cfg) for sc in cells])


def _sweep_report(phase: str, spec, sw) -> None:
    _report(phase, configs=sw.n_configs, backend=sw.backend,
            filter_backend=spec.base.fleet_config().filter_backend,
            compile_s=f"{sw.compile_s:.3f}", run_s=f"{sw.wall_clock_s:.3f}",
            n_truncated=sum(r.n_truncated for r in sw.results),
            timings="smoke (one run), not a benchmark")


def phase_paper_grid(policies=None, loads=PAPER_LOADS, n_ticks=N_TICKS,
                     xval_policies=XVAL_POLICIES, xval_loads=XVAL_LOADS,
                     n_requests=20_000):
    """(a) The paper grid, then the DES reference.  Returns the sweep
    (phase (b) compares against its Metrics)."""
    from repro.fleetsim.validate import cross_validate_spec
    from repro.scenarios import SweepSpec

    spec = SweepSpec(base=_base(n_ticks=n_ticks),
                     policies=tuple(policies) if policies else "registered",
                     loads=tuple(loads), seeds=(0,))
    sw = spec.run_fleetsim()
    _sweep_report("a", spec, sw)
    vspec = SweepSpec(base=_base(n_ticks=n_ticks),
                      policies=tuple(xval_policies), loads=tuple(xval_loads),
                      seeds=(0,))
    t0 = time.perf_counter()
    checks = cross_validate_spec(vspec, n_requests=n_requests,
                                 n_ticks=n_ticks)
    for c in checks:
        print(("[a] PASS " if c.ok else "[a] FAIL ") + c.describe())
    n_ok = sum(c.ok for c in checks)
    _report("a", des_agreement=f"{n_ok}/{len(checks)}",
            des_s=f"{time.perf_counter() - t0:.3f}")
    if n_ok != len(checks):
        raise PhaseFailed(f"(a) {len(checks) - n_ok} of {len(checks)} points "
                          "disagree with the DES")
    return sw


def phase_fused(paper, policies=None, loads=PAPER_LOADS, n_ticks=N_TICKS):
    """(b) The non-stage policies, on the fused core the engine must select
    for them, bit-identical to the matching staged rows of ``paper``."""
    from repro.scenarios import SweepSpec

    if paper.backend != "staged":
        raise PhaseFailed(f"(b) compares against staged rows; (a) ran "
                          f"{paper.backend!r}")
    spec = SweepSpec(base=_base(n_ticks=n_ticks),
                     policies=tuple(policies or non_stage_policies()),
                     loads=tuple(loads), seeds=(0,))
    sw = spec.run_fleetsim()
    _sweep_report("b", spec, sw)
    if sw.backend != "fused":
        raise PhaseFailed(f"(b) the engine selected {sw.backend!r}, not "
                          "'fused'")
    row_of = {(r.policy, r.offered_load): i
              for i, r in enumerate(paper.results)}
    bad = {}
    for i, r in enumerate(sw.results):
        j = row_of[(r.policy, r.offered_load)]
        diff = _mismatched(_row(sw.metrics, i), _row(paper.metrics, j))
        if diff:
            bad[f"{r.policy}@{r.offered_load}"] = diff
    _report("b", bit_identical_rows=f"{len(sw.results) - len(bad)}/"
            f"{len(sw.results)}")
    if bad:
        raise PhaseFailed(f"(b) fused differs from staged: {bad}")
    return sw


def phase_tickfuse(racks=4, policies=None, loads=FABRIC_LOADS,
                   n_ticks=N_TICKS, expect_custom_call=True):
    """(c) The skewed fabric on the core the engine selects (fused on the
    chip): the TickFuse kernel bit-identical to the exact sequential filter
    and (on the chip) compiled into the program; the vectorized filter's
    deviation reported."""
    import jax

    from repro.fleetsim import lower, select_core

    base = _base(racks, n_ticks, hot_rack_weight=3.0,
                 straggler_rack_mult=2.0)
    cells = [replace(base, policy=p, load=ld)
             for p in (policies or non_stage_policies()) for ld in loads]
    # one static config for the grid, with arrival lanes for its hottest load
    hot = replace(base, load=max(loads))
    runs = {}
    for fb in ("tickfuse", "scan", "vectorized"):
        cfg = hot.fleet_config(filter_backend=fb)
        params = _stack_params(cfg, cells)
        t0 = time.perf_counter()
        compiled = lower(cfg, params).compile()
        t1 = time.perf_counter()
        metrics = jax.block_until_ready(compiled(params))
        t2 = time.perf_counter()
        runs[fb] = (compiled, jax.device_get(metrics))
        _report("c", configs=len(cells), racks=racks,
                backend=select_core(cfg),
                filter_backend=fb, compile_s=f"{t1 - t0:.3f}",
                run_s=f"{t2 - t1:.3f}",
                n_truncated=int(runs[fb][1].n_truncated.sum()),
                n_filtered=int(runs[fb][1].n_filtered.sum()),
                timings="smoke (one run), not a benchmark")
    has_kernel = "tpu_custom_call" in runs["tickfuse"][0].as_text()
    diff = _mismatched(runs["tickfuse"][1], runs["scan"][1])
    _report("c", tpu_custom_call=has_kernel,
            tickfuse_bit_identical_to_scan=not diff,
            vectorized_differs_in=_mismatched(runs["tickfuse"][1],
                                              runs["vectorized"][1]))
    if diff:
        raise PhaseFailed(f"(c) tickfuse differs from the sequential filter "
                          f"in {diff}")
    if expect_custom_call and not has_kernel:
        raise PhaseFailed("(c) no tpu_custom_call in the tickfuse program: "
                          "the kernel did not compile for the chip")
    return has_kernel


def phase_four_chips(policies=None, loads=PAPER_LOADS[:5], n_ticks=N_TICKS,
                     devices=4):
    """(4) The paper grid sharded over ``devices`` chips, bit-exact against
    the unsharded vmap on one of them."""
    import jax

    from repro.fleetsim import ShardSpec
    from repro.fleetsim.shard import lower_sharded, plan_grid
    from repro.fleetsim.validate import shard_equivalence
    from repro.scenarios import SweepSpec, registry

    pols = tuple(policies or registry.two_engine_names())
    base = _base(n_ticks=n_ticks)
    shard = ShardSpec(devices=devices)
    # placement: the padded grid's slabs, one per device
    cfg = replace(base, load=max(loads)).fleet_config().with_policy_stages(
        pols)
    cells = [replace(base, policy=p, load=ld) for p in pols for ld in loads]
    plan = plan_grid(_stack_params(cfg, cells), shard)
    t0 = time.perf_counter()
    compiled = lower_sharded(cfg, plan).compile()
    t1 = time.perf_counter()
    met, _ = jax.block_until_ready(compiled(plan.params, plan.mask))
    t2 = time.perf_counter()
    slabs = {str(s.device): s.data.shape[0]
             for s in met.n_arrivals.addressable_shards}
    _report("4", configs=plan.n_grid, padded=plan.n_grid + plan.n_pad,
            devices=devices, compile_s=f"{t1 - t0:.3f}",
            run_s=f"{t2 - t1:.3f}", timings="smoke (one run), not a benchmark")
    _report("4", slab_rows_per_device=slabs)
    if len(slabs) != devices or len(set(slabs.values())) != 1:
        raise PhaseFailed(f"(4) slabs not spread evenly over {devices} "
                          f"devices: {slabs}")
    spec = SweepSpec(base=base, policies=pols, loads=tuple(loads),
                     seeds=(0,))
    checks, hist_ok = shard_equivalence(spec, shard=shard)
    n_ok = sum(c.ok for c in checks)
    for c in checks:
        if not c.ok:
            print("[4] FAIL " + c.describe())
    _report("4", shard_equivalence=f"{n_ok}/{len(checks)}",
            grid_hist_equal=hist_ok)
    if n_ok != len(checks) or not hist_ok:
        raise PhaseFailed("(4) sharded run differs from the unsharded vmap")
    return slabs


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r};"
              " no phase run", file=sys.stderr)
        return 1
    from repro import compile_cache

    print(f"compile cache: {compile_cache.enable()}")
    print(f"device: {devices[0].device_kind} x {len(devices)}", flush=True)
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 chips, found "
                  f"{len(devices)}", file=sys.stderr)
            return 1
        phase_four_chips()
    else:
        paper = phase_paper_grid()
        phase_fused(paper)
        phase_tickfuse()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
