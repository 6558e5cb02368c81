"""The comparison that decides ``correct``: the program's grid rows against
the plain reference (``reference/des.py``).

The program answers each grid row with raw counters and a log-spaced
latency histogram.  This module turns both sides into the same statistics
with its own arithmetic: the reference's latencies are binned into the
program's histogram bins, and both histograms are read by one rule.  It
then holds each number against its limit from the cell's traffic file:

- ``filter_gap``, ``redundant_gap``, ``clone_gap``: the widest gap over
  the steady rows of the filtered share of cloned requests, of the
  redundant responses reaching a client per arrival, and of the cloned
  share of arrivals (the switch's cloning and filtering decisions);
- ``p50_gap``, ``p99_gap``: the widest relative gap over the policies of
  the median and the 99th percentile of latency, each policy's calm rows
  pooled (the latency the servers and clients produce).  A single row of
  5,120 ticks holds too few requests for its own tail, and rows near
  their critical load build queues over the run, so their percentiles
  swing between two samples of the reference itself.

Gaps of single rows' percentiles, of goodput and of the collapse
classification are printed beside them as readings (``READINGS``): no
control moves them by three times what sound runs read.  PERF.md gives
the readings behind each limit.

A row is *saturated* when the reference shows no steady state: delivered
throughput under 90% of offered, or the servers' effective utilisation
(load × served copies per request) or LÆDGE's coordinator CPU at 95% or
more.  The classification and its thresholds are those of the program's
own cross-validation (``fleetsim/validate.py``), copied.  A row is *calm*
when that utilisation is under :data:`CALM_UTIL`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

SATURATION_THR = 0.90
UTIL_CRITICAL = 0.95
#: rows under this effective utilisation carry the pooled percentiles
CALM_UTIL = 0.7
#: LÆDGE: CPU µs per packet and packets per fully cloned request
COORD_CPU_US = 1.5
COORD_PACKETS_PER_CLONE = 4.0
#: program-side collapse signature besides lost goodput: shed arrivals
OVERFLOW_COLLAPSE = 0.02

#: the numbers held against a limit — see PERF.md for both readings of each
NUMBERS = ("filter_gap", "redundant_gap", "clone_gap", "p50_gap", "p99_gap")
#: gaps printed beside them as readings only
READINGS = ("row_p50_gap", "row_p99_gap", "goodput_gap",
            "collapse_mismatch")


def bin_index(lat_us: np.ndarray, lo_us: float, growth: float,
              bins: int) -> np.ndarray:
    """The program's histogram bin of each latency: log-spaced from
    ``lo_us`` by ``growth``, the ends clipped into the first and last."""
    x = np.log(np.maximum(lat_us, lo_us) / lo_us) / math.log(growth)
    return np.clip(x, 0, bins - 1).astype(np.int64)


def hist_quantile(hist: np.ndarray, lo_us: float, growth: float,
                  q: float) -> float:
    """The ``q``-th percentile of a histogram, interpolated geometrically
    inside the bin that holds it."""
    c = np.cumsum(hist)
    if c[-1] == 0:
        return float("nan")
    target = q / 100.0 * c[-1]
    k = int(np.searchsorted(c, target, side="left"))
    below = c[k - 1] if k else 0
    frac = (target - below) / max(hist[k], 1)
    return float(lo_us * growth ** (k + frac))


@dataclass
class RowStats:
    """The statistics of one grid row that both sides report."""

    hist: np.ndarray            # latencies in the window, program's bins
    n_arrivals: int
    n_cloned: int
    n_clone_drops: int
    n_filtered: int
    n_redundant: int
    goodput: float              # delivered / offered throughput
    overflow_share: float = 0.0
    offered_rate: float = 0.0

    @property
    def clone_frac(self) -> float:
        return self.n_cloned / max(self.n_arrivals, 1)

    @property
    def filter_frac(self) -> float:
        return self.n_filtered / self.n_cloned if self.n_cloned else 0.0

    @property
    def redundant_frac(self) -> float:
        return self.n_redundant / max(self.n_arrivals, 1)


def program_stats(m: dict, *, window_us: float,
                  rate_per_us: float) -> RowStats:
    """One row of the program's raw metrics (host numpy) → statistics."""
    n_arr = int(m["n_arrivals"])
    hist = np.asarray(m["hist"])
    return RowStats(
        hist=hist.reshape(-1, hist.shape[-1]).sum(axis=0),
        n_arrivals=n_arr,
        n_cloned=int(m["n_cloned"]),
        n_clone_drops=int(m["n_clone_drops"]),
        n_filtered=int(m["n_filtered"]),
        n_redundant=int(m["n_redundant"]),
        goodput=int(m["n_completed_win"]) / window_us / rate_per_us,
        overflow_share=(int(m["n_overflow"]) + int(m["n_coord_overflow"]))
        / max(n_arr, 1),
    )


def reference_stats(r, lo_us: float, growth: float,
                    bins: int) -> RowStats:
    """One reference run (``des.Result``) → statistics, its latencies
    binned as the program bins its own."""
    return RowStats(
        hist=np.bincount(bin_index(r.latencies_us, lo_us, growth, bins),
                         minlength=bins),
        n_arrivals=r.n_requests,
        n_cloned=r.n_cloned,
        n_clone_drops=r.n_clone_drops,
        n_filtered=r.n_filtered,
        n_redundant=r.n_redundant_at_client,
        goodput=r.throughput_mrps / r.offered_rate_mrps,
        offered_rate=r.offered_rate_mrps,
    )


def utilisation(ref: RowStats, load: float) -> float:
    """The servers' effective utilisation: load × served copies."""
    return load * (1.0 + (ref.n_cloned - ref.n_clone_drops)
                   / max(ref.n_arrivals, 1))


def saturated(ref: RowStats, load: float, coordinator: bool) -> bool:
    """No steady state in the reference (see the module docstring)."""
    coord = (COORD_PACKETS_PER_CLONE * COORD_CPU_US * ref.offered_rate
             if coordinator else 0.0)
    return (ref.goodput < SATURATION_THR
            or utilisation(ref, load) >= UTIL_CRITICAL
            or coord >= UTIL_CRITICAL)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(a), abs(b), 1e-9)


def _finite(g: dict[str, float]) -> dict[str, float]:
    return {k: (v if math.isfinite(v) else math.inf) for k, v in g.items()}


def row_gaps(prog: RowStats, ref: RowStats, *, load: float,
             coordinator: bool, hist: tuple[float, float, int]
             ) -> dict[str, float]:
    """The gap of each statistic in one row.  A statistic that does not
    apply to the row (a saturated row's latency) is left out."""
    lo, growth, _ = hist
    if saturated(ref, load, coordinator):
        if ref.goodput < SATURATION_THR:
            collapsed = (prog.goodput < SATURATION_THR
                         or prog.overflow_share > OVERFLOW_COLLAPSE)
            return {"collapse_mismatch": 0.0 if collapsed else 1.0}
        return {"goodput_gap": _rel(ref.goodput, prog.goodput)}
    q = (lambda s, p: hist_quantile(s.hist, lo, growth, p))
    return _finite({
        "row_p50_gap": _rel(q(ref, 50), q(prog, 50)),
        "row_p99_gap": _rel(q(ref, 99), q(prog, 99)),
        "clone_gap": abs(ref.clone_frac - prog.clone_frac),
        "filter_gap": abs(ref.filter_frac - prog.filter_frac),
        "redundant_gap": abs(ref.redundant_frac - prog.redundant_frac),
        "goodput_gap": _rel(ref.goodput, prog.goodput),
    })


def pooled_gaps(rows: list[tuple[str, float]], progs: list[RowStats],
                refs: list[RowStats], hist: tuple[float, float, int]
                ) -> dict[str, float]:
    """``p50_gap`` and ``p99_gap``: each policy's calm rows pooled, the
    widest relative gap over the policies."""
    lo, growth, _ = hist
    out: dict[str, float] = {}
    for policy in dict.fromkeys(p for p, _ in rows):
        calm = [i for i, (p, load) in enumerate(rows)
                if p == policy
                and not saturated(refs[i], load, policy == "laedge")
                and utilisation(refs[i], load) < CALM_UTIL]
        if not calm:
            continue
        hp = sum(progs[i].hist for i in calm)
        hr = sum(refs[i].hist for i in calm)
        for name, q in (("p50_gap", 50.0), ("p99_gap", 99.0)):
            g = _rel(hist_quantile(hr, lo, growth, q),
                     hist_quantile(hp, lo, growth, q))
            out[name] = max(out.get(name, 0.0),
                            g if math.isfinite(g) else math.inf)
    return out


def call_numbers(rows: list[tuple[str, float]], progs: list[RowStats],
                 refs: list[RowStats], hist: tuple[float, float, int]
                 ) -> dict[str, float]:
    """Every number and reading of one call: ``rows`` are its ``(policy,
    load)`` pairs, with the program's and the reference's statistics."""
    out: dict[str, float] = {}
    for (policy, load), p, r in zip(rows, progs, refs):
        g = row_gaps(p, r, load=load, coordinator=policy == "laedge",
                     hist=hist)
        for k, v in g.items():
            out[k] = max(out.get(k, 0.0), v)
    out.update(pooled_gaps(rows, progs, refs, hist))
    return out


def verdict(numbers: dict[str, float], limits: dict[str, float]
            ) -> tuple[bool, list[tuple[str, float, float]]]:
    """Hold each compared number against its limit.  Every number the
    cell's limits name must have a reading: a call whose rows gave none
    (every row saturated, say) is not correct."""
    lines = [(k, numbers.get(k, math.inf), limits[k]) for k in NUMBERS
             if k in limits]
    ok = bool(lines) and all(v <= lim for _, v, lim in lines)
    return ok, lines
