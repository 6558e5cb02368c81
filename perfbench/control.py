"""The controls of the comparison that decides ``correct``.

    python3 perfbench/control.py --workload testbed.switch5 --seeds 1,2,3 \\
        [--control filter_off]

Each control breaks one guarantee the configuration states
(``reference.des.CONTROLS``): the switch filters the slower response of a
cloned pair (``filter_off``); a request is cloned only when both of its
candidates are tracked idle (``clone_unchecked``); each copy of a request
draws its own execution time at its server (``shared_draw``).  The plain
reference so broken is put in the program's place, at the cell's own size
— the grid of one call, on each seed given — and held against the sound
reference.  Some number of the cell has to come out above its limit, so
that ``correct`` reads false; the numbers are printed beside their
limits, one line per control and seed, and the command exits non-zero if
any reads correct.  It needs no chip: both sides run on the host.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: the control's own stream: an independent sample, as the program is
CONTROL_STREAM = 0x5BD1E995


def control_verdict(cell, seed: int, control: str | None):
    """``(ok, lines, readings)`` of one control (``None``: an independent
    sound sample of the reference) on one call's grid."""
    import run
    from reference import des, pool

    c, t = cell.config, cell.traffic
    svc = t["service"]
    service = des.Service(svc["kind"], svc["params"],
                          jitter_p=svc["jitter_p"],
                          jitter_mult=svc["jitter_mult"])
    spec, overrides = run.build_sweep(cell)
    hist = run.hist_layout(spec, overrides)
    seeds = run.call_seeds(seed, 1, 0, t["seeds_per_call"])
    rows, refs, ctls = [], [], []
    for policy in t["policies"]:
        for load in t["loads"]:
            rate = des.load_to_rate(load, service, c["servers_per_rack"],
                                    c["workers_per_server"])
            for s in seeds:
                rows.append((policy, load))
                kw = dict(policy=policy, load=load, rate_per_us=rate,
                          hist=hist)
                refs.append(pool.task(c, t, seed=s, **kw))
                ctls.append(pool.task(c, t, seed=s ^ CONTROL_STREAM,
                                      control=control, **kw))
    out = pool.run(refs + ctls)
    return run.judge(cell, rows, out[len(refs):], out[:len(refs)], hist)


def main(argv=None) -> int:
    from reference import des

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, three or more")
    ap.add_argument("--control", choices=des.CONTROLS, action="append",
                    help="a control to run (default: every one)")
    args = ap.parse_args(argv)
    import run

    sys.path.insert(0, str(run.ROOT / "src"))
    cell = run.load_cell(args.workload)
    failed_all = True
    for control in args.control or des.CONTROLS:
        for seed in (int(s) for s in args.seeds.split(",")):
            ok, lines, readings = control_verdict(cell, seed, control)
            failed_all &= not ok
            print(json.dumps({"control": control, "seed": seed,
                              "correct": ok,
                              "checks": {k: {"value": v, "limit": lim}
                                         for k, v, lim in lines},
                              "readings": readings}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    raise SystemExit(main())
