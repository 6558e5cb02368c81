"""LÆDGE golden on the testbed rack: every counter and the histogram, exact.

The coordinator stage counts its outstanding copies per server with
integer adds, so any rewrite of that bookkeeping must leave every
statistic bit-identical.  ``tests/golden/fleetsim_coordinator.json`` pins
LÆDGE on the paper's rack (6 servers x 15 workers, 2 clients, 2^10 filter
slots, 2,048 ticks) at two loads: 0.02, where the coordinator CPU keeps up,
and 0.5, where the waiting ring, the drain path and ring overflow all
engage.

Regenerate (only for an intended change of the statistics)::

    PYTHONPATH=src python tests/test_coordinator_golden.py
"""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.workloads import load_to_rate
from repro.fleetsim import (
    POLICY_IDS,
    FleetConfig,
    ServiceSpec,
    make_params,
    simulate,
)

GOLDEN = Path(__file__).parent / "golden" / "fleetsim_coordinator.json"
SEED = 1_618_033_988
LOADS = (0.02, 0.5)


def rack_cfg() -> FleetConfig:
    return FleetConfig(n_servers=6, n_workers=15, n_clients=2,
                       n_filter_slots=2 ** 10, n_ticks=2048,
                       service=ServiceSpec.exponential(25.0)
                       ).with_policy_stages(["laedge"])


def run_laedge(load: float, seed: int = SEED) -> dict:
    """Every ``Metrics`` field of one LÆDGE run, as plain lists."""
    cfg = rack_cfg()
    rate = load_to_rate(load, cfg.service.to_process(), cfg.n_servers,
                        cfg.n_workers)
    params = make_params(cfg, POLICY_IDS["laedge"], rate, seed)
    m = jax.block_until_ready(simulate(cfg, params))
    return {f: np.asarray(getattr(m, f)).tolist() for f in m._fields}


@pytest.mark.parametrize("load", LOADS)
def test_laedge_matches_golden(load):
    case = next(c for c in json.loads(GOLDEN.read_text())["cases"]
                if c["load"] == load)
    got = run_laedge(load, case["seed"])
    assert got.keys() == case["metrics"].keys()
    for field, want in case["metrics"].items():
        assert got[field] == want, (load, field)


def test_golden_engages_the_coordinator_paths():
    """The golden's loads cover what they are there for: at 0.02 nothing
    waits; at 0.5 requests wait, are drained to freed servers, and the
    ring overflows."""
    by_load = {c["load"]: c["metrics"]
               for c in json.loads(GOLDEN.read_text())["cases"]}
    light, heavy = by_load[0.02], by_load[0.5]
    assert light["n_coord_queued"] > 0 and light["n_cloned"] > 0
    assert light["n_coord_pending"] == light["n_coord_overflow"] == 0
    assert heavy["n_coord_pending"] > 0 and heavy["n_coord_overflow"] > 0


if __name__ == "__main__":
    GOLDEN.write_text(json.dumps({
        "cfg": {"n_servers": 6, "n_workers": 15, "n_clients": 2,
                "n_filter_slots": 2 ** 10, "n_ticks": 2048,
                "policy": "laedge", "service": "exponential(25.0)"},
        "cases": [{"load": load, "seed": SEED, "metrics": run_laedge(load)}
                  for load in LOADS]}, indent=1) + "\n")
