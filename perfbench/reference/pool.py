"""Runs of the reference for a call's grid rows, spread over processes.

The reference runs on the host after the window has closed.  Each row is
one independent simulation, so the rows are spread over a few spawned
worker processes; they import only NumPy and this package, never JAX, so
they cannot reach for the chip the parent holds.
"""

from __future__ import annotations

import os
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context

import compare
from reference import des

#: upper bound on the worker processes
MAX_WORKERS = 8


def task(config: dict, traffic: dict, *, policy: str, load: float,
         seed: int, rate_per_us: float, hist: tuple[float, float, int],
         control: str | None = None) -> dict:
    """One row's reference run: the configuration's testbed over the same
    horizon the program simulated, its latencies binned as ``hist``
    (lowest edge µs, growth, bins) says."""
    if config["racks"] != 1:
        raise ValueError("the reference models one ToR; "
                         f"{config['name']} has {config['racks']} racks")
    horizon_us = traffic["n_ticks"] * config["dt_us"]
    return dict(
        policy=policy, load=load, seed=seed,
        servers=config["servers_per_rack"],
        workers=config["workers_per_server"], clients=config["clients"],
        filter_tables=config["filter_tables"],
        filter_slots=config["filter_slots"], costs=config["costs_us"],
        service=traffic["service"],
        n_requests=max(1, round(rate_per_us * horizon_us)),
        horizon_us=horizon_us, hist=hist, control=control)


def run_one(t: dict) -> compare.RowStats:
    svc = t["service"]
    sim = des.Simulator(
        t["policy"],
        des.Service(svc["kind"], svc["params"], jitter_p=svc["jitter_p"],
                    jitter_mult=svc["jitter_mult"]),
        n_servers=t["servers"], n_workers=t["workers"],
        n_clients=t["clients"], n_filter_tables=t["filter_tables"],
        n_filter_slots=t["filter_slots"], control=t["control"],
        costs=des.Costs(**t["costs"]), seed=t["seed"])
    return compare.reference_stats(
        sim.run(t["load"], t["n_requests"], horizon_us=t["horizon_us"]),
        *t["hist"])


def run(tasks: list[dict], workers: int | None = None
        ) -> list[compare.RowStats]:
    """Every task's reference statistics, in order.  ``workers=0`` runs
    them in this process."""
    if workers is None:
        workers = min(MAX_WORKERS, max(1, (os.cpu_count() or 2) - 1))
    if workers == 0 or len(tasks) == 1:
        return [run_one(t) for t in tasks]
    with ProcessPoolExecutor(max_workers=workers,
                             mp_context=get_context("spawn")) as ex:
        return list(ex.map(run_one, tasks))
