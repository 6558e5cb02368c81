"""Plain reference: an event-driven simulator of the NetClone testbed.

A copy of the repository's discrete-event simulator (``repro.core``:
``header``, ``tables``, ``switch``, ``policies``, ``hedging``,
``simulator``), cut to what the benchmark's cells use — Poisson arrivals,
exponential or bimodal service, the seven registered policies — and kept
here so that no change to the program under test can move the yardstick.
It imports nothing of the program.

It draws and answers exactly as ``repro.core.simulator`` does (checked by
the benchmark's tests).  ``run(..., horizon_us=...)`` measures a run the
way a tick engine does: the requests that reach their client inside the
horizon, after its warm-up share.

``control`` breaks one guarantee of the testbed, for the benchmark's
controls (``CONTROLS``): the reference so broken is put in the program's
place, and ``correct`` has to read false.
"""

from __future__ import annotations

import heapq
import itertools
from collections import deque
from dataclasses import dataclass

import numpy as np

CLO_NONE, CLO_ORIG, CLO_CLONE = 0, 1, 2

# event kinds
_REQ_AT_SWITCH = 0
_REQ_AT_SERVER = 1
_SERVER_DONE = 2
_RESP_AT_SWITCH = 3
_RESP_AT_CLIENT = 4
_CLIENT_DONE = 5
_COORD_REQ = 6
_COORD_RESP = 7
_HEDGE_FIRE = 9

_HASH_MULT = 2654435761
_MASK32 = 0xFFFFFFFF

#: each breaks one guarantee the testbed states:
#: ``filter_off`` — the switch filters the slower response of a cloned pair;
#: ``clone_unchecked`` — a request is cloned only when both candidates are
#: tracked idle; ``shared_draw`` — each copy of a request draws its own
#: execution time at its server
CONTROLS = ("filter_off", "clone_unchecked", "shared_draw")
#: policies whose client sends both copies (doubled sender cost)
CLIENT_DUP = ("c-clone",)


# ------------------------------------------------------------- workload ---
class Service:
    """Per-request intrinsic demand plus per-execution randomness: each copy
    of a request draws its own runtime and its own jitter spike."""

    def __init__(self, kind: str, params, jitter_p: float = 0.01,
                 jitter_mult: float = 15.0):
        self.kind, self.params = kind, tuple(float(p) for p in params)
        self.jitter_p, self.jitter_mult = jitter_p, jitter_mult
        if kind == "exponential":
            self.mean = self.params[0]
        elif kind == "bimodal":
            short, long, p_long = self.params
            self.mean = (1 - p_long) * short + p_long * long
        else:
            raise ValueError(f"unknown service kind {kind!r}")

    @property
    def effective_mean(self) -> float:
        return self.mean * (1.0 + self.jitter_p * (self.jitter_mult - 1.0))

    def intrinsic(self, rng, n):
        if self.kind == "exponential":
            return np.full(n, self.mean)
        short, long, p_long = self.params
        return np.where(rng.random(n) < p_long, long, short)

    def execute(self, rng, base: float) -> float:
        if self.kind == "exponential":
            s = float(rng.exponential(base))
        else:
            s = base * float(rng.uniform(0.9, 1.1))
        if self.jitter_p > 0 and rng.random() < self.jitter_p:
            s *= self.jitter_mult
        return s


def load_to_rate(load: float, service: Service, n_servers: int,
                 n_workers: int) -> float:
    """Offered load (share of cluster capacity) → arrival rate (req/µs)."""
    return load * (n_servers * n_workers / service.effective_mean)


# --------------------------------------------------------------- packets ---
@dataclass(slots=True)
class Request:
    req_id: int = -1
    grp: int = -1
    clo: int = CLO_NONE
    idx: int = 0
    dst: int = -1
    t_arrival: float = 0.0
    service: float = 0.0
    client_id: int = 0


@dataclass(slots=True)
class Response:
    req_id: int = -1
    sid: int = -1
    state: int = 0
    clo: int = CLO_NONE
    idx: int = 0
    t_arrival: float = 0.0
    client_id: int = 0


def _clone_of(req: Request, dst: int, clo: int) -> Request:
    return Request(req_id=req.req_id, grp=req.grp, clo=clo, idx=req.idx,
                   dst=dst, t_arrival=req.t_arrival, service=req.service,
                   client_id=req.client_id)


# ---------------------------------------------------------------- tables ---
def fingerprint_hash(req_id: int, n_slots: int) -> int:
    x = (req_id * _HASH_MULT) & _MASK32
    return (x >> 15) % n_slots


class GroupTable:
    """GrpT: ``2·C(n,2)`` ordered candidate pairs of local server ids."""

    def __init__(self, n_servers: int):
        pairs = []
        for a, b in itertools.combinations(range(n_servers), 2):
            pairs.append((a, b))
            pairs.append((b, a))
        self.pairs = np.asarray(pairs, dtype=np.int32)

    @property
    def n_groups(self) -> int:
        return int(self.pairs.shape[0])

    def lookup(self, grp: int) -> tuple[int, int]:
        s1, s2 = self.pairs[grp]
        return int(s1), int(s2)


class StateTable:
    """StateT: the piggybacked queue length of each server (0 == idle).
    With ``idle_check`` off every pair reads idle."""

    idle_check = True

    def __init__(self, n_servers: int):
        self.state = np.zeros(n_servers, dtype=np.int32)

    def update(self, sid: int, qlen: int) -> None:
        self.state[sid] = qlen

    def is_idle_pair(self, s1: int, s2: int) -> bool:
        return (not self.idle_check
                or (self.state[s1] == 0 and self.state[s2] == 0))

    def load(self, sid: int) -> int:
        return int(self.state[sid])


class FilterTables:
    """FilterT: the faster response of a cloned pair parks its id, the
    slower finds it, clears the slot and is dropped; a mismatching occupant
    is overwritten."""

    def __init__(self, n_tables: int, n_slots: int, enabled: bool = True):
        self.tables = np.zeros((n_tables, n_slots), dtype=np.int64)
        self.n_tables, self.n_slots = n_tables, n_slots
        self.enabled = enabled
        self.n_filtered = 0

    def process(self, req_id: int, idx: int) -> bool:
        if not self.enabled:
            return False
        slot = fingerprint_hash(req_id, self.n_slots)
        table = self.tables[idx]
        if table[slot] == req_id:
            table[slot] = 0
            self.n_filtered += 1
            return True
        table[slot] = req_id
        return False


# -------------------------------------------------------------- policies ---
class Policy:
    """One ToR's routing decision.  ``route`` returns ``[(packet,
    switch_delay_µs), ...]``; ``on_response``
    says whether the switch drops a response."""

    name = "abstract"
    needs_coordinator = False
    uses_groups = False
    #: the client draws a filter-table index over every table only for the
    #: switch-filtering policies (NetClone's ``IDX``); others draw table 0
    switch_tables = False

    def __init__(self, n_servers: int, n_filter_tables: int,
                 n_filter_slots: int, filtering: bool):
        self.n_servers = n_servers
        self.pipeline_pass = 0.4
        self.recirculation = 0.4
        self.seq = 0
        self.n_cloned = 0
        self.state_table = StateTable(n_servers)
        self.filter_tables = FilterTables(n_filter_tables, n_filter_slots,
                                          filtering)
        self.grp_table = GroupTable(n_servers)

    @property
    def n_groups(self) -> int:
        return self.grp_table.n_groups if self.uses_groups else 0

    def _stamp(self, req):
        self.seq += 1
        req.req_id = self.seq

    def on_response(self, resp) -> bool:
        self.state_table.update(resp.sid, resp.state)
        return False


class Baseline(Policy):
    name = "baseline"

    def route(self, req, rng):
        self._stamp(req)
        req.dst = int(rng.integers(self.n_servers))
        req.clo = CLO_NONE
        return [(req, self.pipeline_pass)]


class CClone(Policy):
    name = "c-clone"

    def route(self, req, rng):
        self._stamp(req)
        k = self.n_servers
        i = int(rng.integers(k))
        j = (i + 1 + int(rng.integers(k - 1))) % k
        req.dst, req.clo = i, CLO_NONE
        self.n_cloned += 1
        p = self.pipeline_pass
        return [(req, p), (_clone_of(req, j, CLO_NONE), p)]


class NetClone(Policy):
    """Algorithm 1: clone iff both candidates of the group are tracked
    idle; filter the slower response."""

    name = "netclone"
    uses_groups = True
    switch_tables = True

    def route(self, req, rng):
        self._stamp(req)
        s1, s2 = self.grp_table.lookup(req.grp)
        req.dst = s1
        p = self.pipeline_pass
        if self.state_table.is_idle_pair(s1, s2):
            req.clo = CLO_ORIG
            self.n_cloned += 1
            return [(req, p),
                    (_clone_of(req, s2, CLO_CLONE), p + self.recirculation)]
        req.clo = CLO_NONE
        return [(req, p)]

    def on_response(self, resp):
        self.state_table.update(resp.sid, resp.state)
        if resp.clo != CLO_NONE:
            return self.filter_tables.process(resp.req_id, resp.idx)
        return False


class RackSched(Policy):
    """Power-of-two-choices JSQ on piggybacked queue lengths."""

    name = "racksched"

    def route(self, req, rng):
        self._stamp(req)
        k = self.n_servers
        i = int(rng.integers(k))
        j = (i + 1 + int(rng.integers(k - 1))) % k
        st = self.state_table
        req.dst = i if st.load(i) <= st.load(j) else j
        req.clo = CLO_NONE
        return [(req, self.pipeline_pass)]


class NetCloneRackSched(NetClone):
    """§3.7: an idle-idle pair clones, otherwise JSQ between the pair."""

    name = "netclone+racksched"

    def route(self, req, rng):
        self._stamp(req)
        s1, s2 = self.grp_table.lookup(req.grp)
        p = self.pipeline_pass
        st = self.state_table
        if st.is_idle_pair(s1, s2):
            req.dst, req.clo = s1, CLO_ORIG
            self.n_cloned += 1
            return [(req, p),
                    (_clone_of(req, s2, CLO_CLONE), p + self.recirculation)]
        req.dst = s1 if st.load(s1) <= st.load(s2) else s2
        req.clo = CLO_NONE
        return [(req, p)]


class Laedge(Policy):
    """LÆDGE: the switch forwards to a CPU coordinator node (the simulator
    runs its dispatch: clone iff ≥2 idle, forward if 1, queue if 0)."""

    name = "laedge"
    needs_coordinator = True


class Hedge(Policy):
    """Delayed hedging: the duplicate goes to the pair's second server
    ``delay_us`` after arrival unless a response came back first."""

    name = "hedge"
    uses_groups = True

    def __init__(self, *a, delay_us: float = 75.0, **kw):
        super().__init__(*a, **kw)
        self.delay_us = delay_us
        self._outstanding: dict[int, tuple[float, int, Request]] = {}

    def route(self, req, rng):
        self._stamp(req)
        s1, s2 = self.grp_table.lookup(req.grp)
        req.dst, req.clo = s1, CLO_ORIG
        self._outstanding[req.req_id] = (self.delay_us, s2, req)
        return [(req, self.pipeline_pass)]

    def on_response(self, resp):
        self._outstanding.pop(resp.req_id, None)
        if resp.clo != CLO_NONE:
            return self.filter_tables.process(resp.req_id, resp.idx)
        return False


POLICIES = {p.name: p for p in (Baseline, CClone, NetClone, RackSched,
                                 NetCloneRackSched, Laedge, Hedge)}


# ------------------------------------------------------------- simulator ---
@dataclass
class Costs:
    """Transport and processing latencies (µs) of the testbed."""

    link: float = 0.5
    server_overhead: float = 1.0
    client_rx: float = 0.68
    client_tx: float = 0.15
    coord_cpu: float = 1.5
    pipeline_pass: float = 0.4


@dataclass
class Result:
    """What one run reports: counters and the window's latencies (µs)."""

    policy: str
    n_requests: int
    n_completed: int
    n_cloned: int
    n_clone_drops: int
    n_filtered: int
    n_redundant_at_client: int
    throughput_mrps: float
    offered_rate_mrps: float
    latencies_us: np.ndarray


class _Server:
    __slots__ = ("queue", "free_workers", "n_workers")

    def __init__(self, n_workers):
        self.queue = deque()
        self.free_workers = n_workers
        self.n_workers = n_workers


class Simulator:
    """The testbed: one ToR switch, its servers and clients."""

    def __init__(self, policy: str, service: Service, *, n_servers: int = 6,
                 n_workers: int = 15, n_clients: int = 2,
                 n_filter_tables: int = 2, n_filter_slots: int = 2 ** 17,
                 control: str | None = None, seed: int = 0,
                 costs: Costs | None = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.policy_name = policy
        self.n_servers = n_servers
        self.n_workers = n_workers
        self.service = service
        self.costs = costs or Costs()
        self.rng = np.random.default_rng(seed)
        self.n_clients = n_clients
        self.tor = POLICIES[policy](n_servers, n_filter_tables,
                                    n_filter_slots, control != "filter_off")
        self.tor.pipeline_pass = self.tor.recirculation = \
            self.costs.pipeline_pass
        self.tor.state_table.idle_check = control != "clone_unchecked"
        #: the copies of one request share one execution draw (a control)
        self._drawn: dict[int, float] | None = (
            {} if control == "shared_draw" else None)
        self.servers = [_Server(n_workers) for _ in range(n_servers)]
        self.client_busy = [0.0] * self.n_clients
        # LÆDGE coordinator state
        self._coord_busy_until = 0.0
        self._coord_pending: deque = deque()
        self._coord_outstanding = np.zeros(n_servers, dtype=np.int64)
        self._coord_seen: set[int] = set()
        self._coord_absorbed = 0
        self.n_cloned_coord = 0
        self.n_clone_drops = 0
        self.n_redundant_at_client = 0

    # ------------------------------------------------------------- utils --
    def _push(self, heap, t, kind, payload):
        self._evseq += 1
        heapq.heappush(heap, (t, self._evseq, kind, payload))

    def run(self, offered_load: float, n_requests: int,
            warmup_frac: float = 0.1, cooldown_frac: float = 0.05,
            horizon_us: float | None = None) -> Result:
        """Simulate ``n_requests`` arrivals and drain them.  The window
        is the repository simulator's (arrivals between ``warmup_frac`` and
        ``1 - cooldown_frac`` of their span) unless ``horizon_us`` is
        given: then it is a run of that length measured as a tick engine
        measures it, requests that reach their client between
        ``warmup_frac`` of the horizon and its end."""
        c = self.costs
        rng = self.rng
        tor = self.tor
        rate = load_to_rate(offered_load, self.service, self.n_servers,
                            self.n_workers)
        arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
        services = self.service.intrinsic(rng, n_requests)
        n_groups = tor.n_groups
        grps = (rng.integers(0, n_groups, n_requests) if n_groups
                else np.zeros(n_requests, dtype=np.int64))
        n_tables = tor.filter_tables.n_tables if tor.switch_tables else 1
        idxs = rng.integers(0, n_tables, n_requests)
        client_ids = rng.integers(0, self.n_clients, n_requests)

        heap: list = []
        self._evseq = 0
        latencies = np.full(n_requests, np.nan)
        first_seen = np.zeros(n_requests, dtype=bool)
        done_t = np.full(n_requests, np.nan)
        tx = c.client_tx * (2.0 if self.policy_name in CLIENT_DUP else 1.0)
        for i in range(n_requests):
            r = Request(grp=int(grps[i]), idx=int(idxs[i]),
                        t_arrival=float(arrivals[i]),
                        service=float(services[i]),
                        client_id=int(client_ids[i]))
            self._push(heap, arrivals[i] + tx + c.link, _REQ_AT_SWITCH,
                       (i, r))

        needs_coord = tor.needs_coordinator
        while heap:
            t, _, kind, payload = heapq.heappop(heap)

            if kind == _REQ_AT_SWITCH:
                i, req = payload
                if needs_coord:
                    self._push(heap, t + tor.pipeline_pass + c.link,
                               _COORD_REQ, (i, req))
                    continue
                for pkt, sw_delay in tor.route(req, rng):
                    self._push(heap, t + sw_delay + c.link, _REQ_AT_SERVER,
                               (i, pkt))
                if tor.name == "hedge":
                    self._push(heap, t + tor.delay_us, _HEDGE_FIRE,
                               (i, req.req_id))
                continue

            if kind == _HEDGE_FIRE:
                i, rid = payload
                entry = tor._outstanding.pop(rid, None)
                if entry is not None:
                    _due, dst2, req0 = entry
                    clone = _clone_of(req0, dst2, CLO_CLONE)
                    tor.n_cloned += 1
                    self._push(heap, t + tor.pipeline_pass + c.link,
                               _REQ_AT_SERVER, (i, clone))
                continue

            if kind == _COORD_REQ:
                i, req = payload
                done = max(t, self._coord_busy_until) + c.coord_cpu
                self._coord_busy_until = done
                self._dispatch_laedge(heap, done, i, req, rng)
                continue

            if kind == _REQ_AT_SERVER:
                i, req = payload
                srv = self.servers[req.dst]
                if req.clo == CLO_CLONE and len(srv.queue) > 0:
                    self.n_clone_drops += 1
                    continue
                if srv.free_workers > 0:
                    srv.free_workers -= 1
                    exec_t = self._execute(rng, req)
                    self._push(heap, t + c.server_overhead + exec_t,
                               _SERVER_DONE, (i, req, req.dst))
                else:
                    srv.queue.append((i, req, t))
                continue

            if kind == _SERVER_DONE:
                i, req, sid = payload
                srv = self.servers[sid]
                if srv.queue:
                    j, nxt, _tq = srv.queue.popleft()
                    exec_t = self._execute(rng, nxt)
                    self._push(heap, t + c.server_overhead + exec_t,
                               _SERVER_DONE, (j, nxt, sid))
                else:
                    srv.free_workers += 1
                resp = Response(req_id=req.req_id, sid=sid,
                                state=len(srv.queue), clo=req.clo,
                                idx=req.idx, t_arrival=req.t_arrival,
                                client_id=req.client_id)
                self._push(heap, t + c.link, _RESP_AT_SWITCH, (i, resp))
                continue

            if kind == _RESP_AT_SWITCH:
                i, resp = payload
                if needs_coord:
                    self._push(heap, t + tor.pipeline_pass + c.link,
                               _COORD_RESP, (i, resp))
                    continue
                if not tor.on_response(resp):
                    self._push(heap, t + tor.pipeline_pass + c.link,
                               _RESP_AT_CLIENT, (i, resp))
                continue

            if kind == _COORD_RESP:
                i, resp = payload
                done = max(t, self._coord_busy_until) + c.coord_cpu
                self._coord_busy_until = done
                self._coord_outstanding[resp.sid] -= 1
                self._drain_laedge(heap, done, rng)
                if resp.req_id in self._coord_seen:
                    self._coord_absorbed += 1
                    continue
                self._coord_seen.add(resp.req_id)
                self._push(heap, done + c.link, _RESP_AT_CLIENT, (i, resp))
                continue

            if kind == _RESP_AT_CLIENT:
                i, resp = payload
                start = max(t, self.client_busy[resp.client_id])
                done = start + c.client_rx
                self.client_busy[resp.client_id] = done
                if first_seen[i]:
                    self.n_redundant_at_client += 1
                    continue
                first_seen[i] = True
                self._push(heap, done, _CLIENT_DONE, (i, resp))
                continue

            if kind == _CLIENT_DONE:
                i, resp = payload
                done_t[i] = t
                latencies[i] = t - resp.t_arrival
                continue

        if horizon_us is not None:
            return self._collect_horizon(rate, arrivals, latencies, done_t,
                                         warmup_frac * horizon_us,
                                         horizon_us)
        return self._collect(rate, arrivals, latencies, done_t,
                             warmup_frac, cooldown_frac)

    def _execute(self, rng, req) -> float:
        if self._drawn is None:
            return self.service.execute(rng, req.service)
        if req.req_id not in self._drawn:
            self._drawn[req.req_id] = self.service.execute(rng, req.service)
        return self._drawn[req.req_id]

    # ------------------------------------------------------ LÆDGE paths --
    def _laedge_idle(self) -> list[int]:
        return [s for s in range(self.n_servers)
                if self._coord_outstanding[s] < self.servers[s].n_workers]

    def _dispatch_laedge(self, heap, t, i, req, rng):
        c = self.costs
        idle = self._laedge_idle()
        if len(idle) >= 2:
            picks = rng.choice(len(idle), size=2, replace=False)
            s1, s2 = idle[picks[0]], idle[picks[1]]
            req.dst = s1
            self.n_cloned_coord += 1
            dup = Request(req_id=i + 1, grp=req.grp, clo=CLO_NONE,
                          idx=req.idx, dst=s2, t_arrival=req.t_arrival,
                          service=req.service, client_id=req.client_id)
            req.req_id = i + 1
            self._coord_outstanding[s1] += 1
            self._coord_outstanding[s2] += 1
            t2 = self._coord_busy_until = (
                max(t, self._coord_busy_until) + c.coord_cpu)
            self._push(heap, t + c.link, _REQ_AT_SERVER, (i, req))
            self._push(heap, t2 + c.link, _REQ_AT_SERVER, (i, dup))
        elif len(idle) == 1:
            req.dst = idle[0]
            req.req_id = i + 1
            self._coord_outstanding[idle[0]] += 1
            self._push(heap, t + c.link, _REQ_AT_SERVER, (i, req))
        else:
            req.req_id = i + 1
            self._coord_pending.append((i, req))

    def _drain_laedge(self, heap, t, rng):
        c = self.costs
        while self._coord_pending:
            idle = self._laedge_idle()
            if not idle:
                return
            i, req = self._coord_pending.popleft()
            req.dst = idle[int(rng.integers(len(idle)))]
            self._coord_outstanding[req.dst] += 1
            t = self._coord_busy_until = (
                max(t, self._coord_busy_until) + c.coord_cpu)
            self._push(heap, t + c.link, _REQ_AT_SERVER, (i, req))

    # ----------------------------------------------------------- metrics --
    def _collect(self, rate, arrivals, lat, done_t, warm, cool):
        span = arrivals[-1] - arrivals[0]
        t0 = arrivals[0] + warm * span
        t1 = arrivals[-1] - cool * span
        in_win = (arrivals >= t0) & (arrivals <= t1) & ~np.isnan(lat)
        comp_in_win = (done_t >= t0) & (done_t <= t1)
        return self._result(rate, arrivals, lat, in_win,
                            comp_in_win.sum() / (t1 - t0) if t1 > t0
                            else 0.0)

    def _collect_horizon(self, rate, arrivals, lat, done_t, t0, t1):
        in_win = (done_t >= t0) & (done_t <= t1)
        return self._result(rate, arrivals, lat, in_win,
                            in_win.sum() / (t1 - t0))

    def _result(self, rate, arrivals, lat, in_win, thr):
        if self.tor.needs_coordinator:
            n_cloned, n_filtered = self.n_cloned_coord, self._coord_absorbed
        else:
            n_cloned = self.tor.n_cloned
            n_filtered = self.tor.filter_tables.n_filtered
        return Result(
            policy=self.policy_name,
            n_requests=len(arrivals),
            n_completed=int((~np.isnan(lat)).sum()),
            n_cloned=n_cloned,
            n_clone_drops=self.n_clone_drops,
            n_filtered=n_filtered,
            n_redundant_at_client=self.n_redundant_at_client,
            throughput_mrps=float(thr),
            offered_rate_mrps=rate,
            latencies_us=lat[in_win],
        )
