"""Plain reference of the fabric simulator: the yardstick ``correct`` is
decided against.

A straightforward float64 implementation of the static-jobs engine
semantics of arXiv:2603.04424 as this repository models them: a fat-tree
fabric, ring all-reduce per BSP job, ``compact`` or ``striped``
placement, per-link AR(1) background congestion with the arrival-burst
derate and the queue-buildup kick, and co-tenant sharing of each shared
link by ``maxmin``, ``wfq`` or ``strict_priority`` over the tenants whose
collectives (this round's, or a recorded busy segment of an earlier one)
overlap the owner's window. A job with ``pacing`` runs the paper's
bounded pacing (sections 4.3 and 5.3): one release clock and one
controller per rank (:class:`Pacer`). It imports nothing of the program
and reads a scenario as the plain dict a configuration file holds
(``Scenario.to_dict`` form). Anything outside that envelope raises
``ValueError``.

``quantize`` rounds every per-step quantity (compute samples, link
utilization, efficiencies, shares, collective and step times, a
controller's observations and delays) to a lower precision: the control
the checks must fail. Clocks stay in float64.
"""
from __future__ import annotations

import math
import random
from typing import Callable, Dict, Iterator, List, Optional

import numpy as np

FAIRNESS = ("maxmin", "wfq", "strict_priority")
RESIDUAL_SHARE = 1e-6


def bfloat16(x: float) -> float:
    """Round to the nearest bfloat16 (the control's precision)."""
    import ml_dtypes
    return float(ml_dtypes.bfloat16(x))


# -- allocators (progressive filling, stable ascending order) ---------------


def maxmin_shares(demands: List[float], capacity: float = 1.0) -> List[float]:
    n = len(demands)
    alloc = [0.0] * n
    remaining = capacity
    for pos, j in enumerate(sorted(range(n), key=demands.__getitem__)):
        fair = remaining / (n - pos)
        give = demands[j] if demands[j] < fair else fair
        alloc[j] = give
        remaining -= give
    return alloc


def wfq_shares(demands: List[float], weights: List[float],
               capacity: float = 1.0) -> List[float]:
    n = len(demands)
    alloc = [0.0] * n
    w_left = 0.0
    for w in weights:
        w_left += w
    remaining = capacity
    for j in sorted(range(n), key=lambda j: demands[j] / weights[j]):
        w = weights[j]
        fair = remaining * w / w_left if w_left > 0.0 else remaining
        give = demands[j] if demands[j] < fair else fair
        alloc[j] = give
        remaining -= give
        w_left -= w
    return alloc


def strict_priority_shares(demands: List[float], priorities: List[float],
                           capacity: float = 1.0) -> List[float]:
    n = len(demands)
    alloc = [0.0] * n
    remaining = capacity
    for prio in sorted(set(priorities), reverse=True):
        idx = [j for j in range(n) if priorities[j] == prio]
        for j, a in zip(idx, maxmin_shares([demands[j] for j in idx],
                                           remaining)):
            alloc[j] = a
            remaining -= a
        if remaining < 0.0:
            remaining = 0.0
    return alloc


def link_share(fairness: str, d_i: float, own_weight: float,
               own_priority: float, owners: List[tuple]) -> float:
    """The owner's share of one link: every co-tenant owner is one flow
    demanding the fraction of the owner's window it occupies (capped at
    1), the owner demands the whole link. ``owners`` holds
    ``(overlap_s, weight, priority)``."""
    demands = [1.0] + [min(1.0, ov / d_i) for ov, _, _ in owners]
    if fairness == "wfq":
        return wfq_shares(demands, [own_weight] + [w for _, w, _ in owners])[0]
    if fairness == "strict_priority":
        share = strict_priority_shares(
            demands, [own_priority] + [p for _, _, p in owners])[0]
        return share if share > RESIDUAL_SHARE else RESIDUAL_SHARE
    return maxmin_shares(demands)[0]


# -- fabric, placement, schedule --------------------------------------------


def _check(scn: dict) -> None:
    topo = scn["topology"]
    if topo["kind"] != "fat_tree":
        raise ValueError(f"reference runs fat_tree fabrics, not "
                         f"{topo['kind']!r}")
    if scn.get("events") is not None or not scn.get("jobs"):
        raise ValueError("reference runs static job populations only")
    pol = scn["policies"]
    if pol["fairness"] not in FAIRNESS:
        raise ValueError(f"reference fairness is one of {FAIRNESS}")
    if pol["routing"] != "ecmp_static":
        raise ValueError("reference routing is ecmp_static")
    for job in scn["jobs"]:
        bad = [k for k, ok in (
            ("kind", job.get("kind", "training") == "training"),
            ("algo", job["algo"] == "ring"),
            ("placement", job["placement"] in ("compact", "striped")),
            ("nodes", job["nodes"] is None),
            ("seed", job["seed"] is None))
            if not ok]
        if bad:
            raise ValueError(f"job {job['name']!r}: reference does not "
                             f"run {bad}")


def fat_tree_links(topo: dict) -> Dict[str, tuple]:
    """name -> (bandwidth B/s, latency s, shared), in the fabric's link
    order (leaf, up-link per leaf, then the spine)."""
    n, npl = topo["n_nodes"], topo["nodes_per_leaf"]
    bw, lat, over = topo["leaf_bw"], topo["latency_s"], topo["oversubscription"]
    links = {}
    for leaf in range(-(-n // npl)):
        links[f"leaf{leaf}"] = (bw * 1e9, lat, False)
        links[f"up{leaf}"] = (bw * npl / over * 1e9, lat, True)
    links["spine"] = (bw * n / over * 1e9, 2 * lat, True)
    return links


def place(policy: str, n_nodes: int, stride: int, n: int,
          taken: set) -> List[int]:
    free = [i for i in range(n_nodes) if i not in taken]
    if n > len(free):
        raise ValueError(f"need {n} nodes, {len(free)} free")
    if policy == "compact":
        return free[:n]
    out: List[int] = []                 # striped: fixed stride, wrapping
    offset = 0
    while len(out) < n and free:
        for node in free[offset::stride]:
            if len(out) == n:
                break
            out.append(node)
            free.remove(node)
        offset = (offset + 1) % stride
    return out


class Ring:
    """Ring all-reduce: 2(n-1) steps, each as slow as its slowest link;
    a shared link carries every hop that crosses it at once."""

    def __init__(self, nodes: List[int], nbytes: float, npl: int,
                 links: Dict[str, tuple]):
        n = len(nodes)
        flows: Dict[str, int] = {}
        for r in range(n):
            a, b = nodes[r] // npl, nodes[(r + 1) % n] // npl
            for ln in ([f"leaf{a}"] if a == b
                       else [f"up{a}", "spine", f"up{b}"]):
                flows[ln] = flows.get(ln, 0) + 1
        chunk = nbytes / n
        self.steps = 2 * (n - 1)
        self.entries = []
        self.shared = []
        for ln, f in flows.items():
            bw, lat, shared = links[ln]
            self.entries.append((ln, (f if shared else 1) * chunk, bw, lat))
            if shared:
                self.shared.append(ln)

    def total_s(self, eff: Optional[Dict[str, float]]) -> float:
        worst = 0.0
        for ln, num, bw, lat in self.entries:
            t = num / (bw if eff is None else bw * eff.get(ln, 1.0)) + lat
            if t > worst:
                worst = t
        return worst * self.steps


class Compute:
    """Per-rank compute time: persistent locality, lognormal jitter,
    Markov on/off spikes, drawn from ``random.Random(seed)`` with the
    Box-Muller pair cache carried across iterations."""

    def __init__(self, cfg: dict, n: int, seed: int):
        self.cfg = cfg
        self.rnd = random.Random(seed).random
        self.scale = [cfg["base_compute_s"]
                      * (1.0 + cfg["locality_spread"] * self.rnd())
                      for _ in range(n)]
        self.spiking = [0.0] * n
        self.g_next = None

    def sample(self) -> List[float]:
        cfg, rnd, spiking = self.cfg, self.rnd, self.spiking
        out = []
        for r, scale in enumerate(self.scale):
            s = spiking[r]
            if s:
                if rnd() < cfg["spike_exit_prob"]:
                    spiking[r] = s = 0.0
            elif rnd() < cfg["spike_prob"]:
                heavy = rnd() < cfg["heavy_frac"]
                spiking[r] = s = cfg["heavy_mult"] if heavy \
                    else cfg["spike_mult"]
            z, self.g_next = _gauss(rnd, self.g_next)
            t = scale * math.exp(z * cfg["jitter_sigma"])
            if s:
                t *= s
            out.append(t)
        return out


def _gauss(rnd, g_next):
    """One Box-Muller draw with the pair cache: returns (z, next cache)."""
    if g_next is not None:
        return g_next, None
    x2pi = rnd() * (2.0 * math.pi)
    g2rad = math.sqrt(-2.0 * math.log(1.0 - rnd()))
    return math.cos(x2pi) * g2rad, math.sin(x2pi) * g2rad


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


class Pacer:
    """One rank's bounded-pacing controller. It sees only its own barrier
    wait and step time. Over a rolling window of ``window`` waits,
    earliness values (wait plus the delay it held) and steps, once
    ``warmup_iters`` observations and two columns are in: where the
    median wait, or a high wait CV with the newest wait, is above the
    thresholds and every earliness in the window is positive, the delay
    becomes ``gain`` times the smallest earliness; otherwise it decays
    by ``decay``, to zero below a millionth of the median step. The rank
    is held back by that delay, at most ``max_delay_frac`` times the
    median step."""

    def __init__(self, cfg: dict, q: Callable[[float], float]):
        self.cfg = cfg
        self.q = q
        self.waits: List[float] = []
        self.early: List[float] = []
        self.steps: List[float] = []
        self.delay = 0.0                # held delay, before the bound
        self.seen = 0

    def observe(self, wait: float, step: float) -> None:
        q, w = self.q, self.cfg["window"]
        wait = q(wait if wait > 0.0 else 0.0)
        self.waits = (self.waits + [wait])[-w:]
        self.early = (self.early + [q(wait + self.delay)])[-w:]
        self.steps = (self.steps + [q(step if step > 0.0 else 0.0)])[-w:]
        self.seen += 1

    def decide(self) -> float:
        """The delay before this rank's next release."""
        cfg, q, waits = self.cfg, self.q, self.waits
        if not cfg["enabled"] or self.seen < cfg["warmup_iters"] \
                or len(waits) < 2:
            return 0.0
        n = len(waits)
        mean = sum(waits) / n
        cv = 0.0
        if mean > 0.0:
            cv = math.sqrt(sum((x - mean) * (x - mean)
                               for x in waits) / n) / mean
        med_wait, med_step = _median(waits), _median(self.steps)
        rel_med = med_wait / med_step if med_step > 0.0 else 0.0
        rel_last = waits[-1] / med_step if med_step > 0.0 else 0.0
        imbalanced = rel_med > cfg["skew_threshold"] or (
            cv > cfg["cv_threshold"] and rel_last > cfg["skew_threshold"])
        least = min(self.early)
        if imbalanced and least > 0.0:
            self.delay = q(cfg["gain"] * least)
        else:
            self.delay = q(self.delay * cfg["decay"])
            if self.delay < 1e-6 * max(med_step, 1e-9):
                self.delay = 0.0
        return q(min(self.delay, cfg["max_delay_frac"] * med_step))


def _activity(users, s_i: float, e_i: float, win, seg: np.ndarray,
              seg_ov: np.ndarray) -> Dict[int, float]:
    """Per co-tenant owner, the time its traffic overlaps the window
    ``[s_i, e_i)`` on a link that ``users`` use: this round's collective
    first, then each recorded segment (``seg`` rows ``start, end,
    owner``, overlaps ``seg_ov``), summed in that order; owners keyed in
    the order first met."""
    act: Dict[int, float] = {}
    for k in users:
        ov = min(e_i, win[k][1]) - max(s_i, win[k][0])
        if ov > 0.0:
            act[k] = ov
    owner = seg[:, 2]
    hit = np.nonzero((seg_ov > 0.0) & np.isin(owner, users))[0]
    if hit.size:
        ks, ovs = owner[hit], seg_ov[hit]
        _, first = np.unique(ks, return_index=True)
        for k in ks[np.sort(first)]:
            k = int(k)
            run = ovs[ks == k]
            if k in act:
                run = np.concatenate([[act[k]], run])
            act[k] = float(np.cumsum(run)[-1])   # left to right
    return act


# -- the engine loop --------------------------------------------------------


def steps(scn: dict, quantize: Optional[Callable[[float], float]] = None
          ) -> Iterator[np.ndarray]:
    """Yield every iteration's step time per job (finish minus the
    previous finish), in job order, for ``scn["iters"]`` iterations."""
    _check(scn)
    q = quantize or (lambda x: x)
    topo, cc, fairness = scn["topology"], scn["congestion"], \
        scn["policies"]["fairness"]
    npl = topo["nodes_per_leaf"]
    links = fat_tree_links(topo)
    base = scn["base_seed"]
    specs = scn["jobs"]
    J = len(specs)
    taken: set = set()
    rings, comps, spans_leaves = [], [], []
    for j, spec in enumerate(specs):
        nodes = place(spec["placement"], topo["n_nodes"], npl,
                      spec["n_ranks"], taken)
        taken.update(nodes)
        rings.append(Ring(nodes, spec["grad_bytes"], npl, links))
        comps.append(Compute(spec["stragglers"], spec["n_ranks"],
                             base + 1 + 1009 * j))
        span = spec.get("spanning_override")
        spans_leaves.append(len({nd // npl for nd in nodes})
                            if span is None else span)
    floor = [max(r.total_s(None), 1e-9) for r in rings]
    weights = [float(s["weight"]) for s in specs]
    prios = [float(s["priority"]) for s in specs]
    # A link's co-tenant traffic is that of the jobs that use it, so two
    # links that the same jobs use see the same overlaps and get the same
    # share: job i's shared links, grouped by the co-tenants using them.
    uses = [set(r.shared) for r in rings]
    by_users = []
    for i in range(J):
        groups: Dict[tuple, List[str]] = {}
        for ln in rings[i].shared:
            users = tuple(k for k in range(J) if k != i and ln in uses[k])
            groups.setdefault(users, []).append(ln)
        by_users.append(groups)

    shared = [ln for ln, (_, _, sh) in links.items() if sh]
    u = {ln: cc["u_mean"] for ln in shared}
    rnd = random.Random(base + 2).random
    g_next = None
    rho = cc["u_rho"]
    drift, iscale = (1 - rho) * cc["u_mean"], (1 - rho) ** 0.5
    # busy segments (start, end, owner) of past collectives, in the order
    # they were recorded; each holds every shared link its owner uses
    segments: List[tuple] = []
    release = [0.0] * J
    prev = [0.0] * J
    # a paced job releases each rank on its own clock
    pacers = [None if s["pacing"] is None
              else [Pacer(s["pacing"], q) for _ in range(s["n_ranks"])]
              for s in specs]
    rank_release = [[0.0] * s["n_ranks"] for s in specs]
    arrival: List[List[float]] = [[] for _ in specs]

    for t in range(scn["iters"]):
        # 1. arrival windows
        first, last, skew = [], [], []
        for j in range(J):
            c = [q(x) for x in comps[j].sample()]
            if pacers[j] is None:
                first.append(release[j] + min(c))
                last.append(release[j] + max(c))
            else:
                arrival[j] = [r + x for r, x in zip(rank_release[j], c)]
                first.append(min(arrival[j]))
                last.append(max(arrival[j]))
            skew.append((last[j] - first[j]) / floor[j])
        # 2. background congestion, one AR(1) step per shared link
        for ln in shared:
            z, g_next = _gauss(rnd, g_next)
            x = rho * u[ln] + drift + iscale * (z * cc["u_sigma"])
            u[ln] = q(0.0 if x < 0.0 else cc["u_max"] if x > cc["u_max"]
                      else x)
        effs = []
        for j in range(J):
            denom = (1.0 + cc["k_burst"] * max(0.0, skew[j])) \
                * (1.0 + cc["ecmp_k"] * max(0, spans_leaves[j] - 1))
            effs.append({ln: q(max(1e-3, (1.0 - v) / denom))
                         for ln, v in u.items()})
        # 3. collective times; co-tenants split overlapping shared links
        dur = [q(rings[j].total_s(effs[j])) for j in range(J)]
        if J > 1:
            dur0 = dur
            win = [(last[k], last[k] + dur0[k]) for k in range(J)]
            seg = np.array(segments).reshape(-1, 3)
            new_effs = []
            for i in range(J):
                s_i, e_i = win[i]
                eff = effs[i]
                if dur0[i] > 0.0:
                    seg_ov = np.minimum(e_i, seg[:, 1]) \
                        - np.maximum(s_i, seg[:, 0])
                    for users, lns in by_users[i].items():
                        act = _activity(users, s_i, e_i, win, seg, seg_ov)
                        if not act:
                            continue
                        share = q(link_share(
                            fairness, dur0[i], weights[i], prios[i],
                            [(ov, weights[k], prios[k])
                             for k, ov in act.items()]))
                        if share < 1.0:
                            if eff is effs[i]:
                                eff = dict(eff)
                            for ln in lns:
                                eff[ln] = q(effs[i][ln] * share)
                new_effs.append(eff)
            dur = [q(rings[j].total_s(new_effs[j])) for j in range(J)]
            # record busy segments; drop those every co-tenant has passed
            fin = [last[j] + dur[j] for j in range(J)]
            thr = [min(f for j, f in enumerate(fin) if j != k)
                   for k in range(J)]
            segments += [(last[i], last[i] + dur[i], i) for i in range(J)
                         if rings[i].shared]
            segments = [s for s in segments if s[1] > thr[s[2]]]
        # 4. queue-buildup kick per job
        for j in range(J):
            if cc["k_kick"] > 0.0 and skew[j] > 0.0:
                kk = cc["k_kick"] * skew[j]
                for ln, v in u.items():
                    v = v + kk * (1.0 - v)
                    u[ln] = q(cc["u_max"] if v > cc["u_max"] else v)
        # 5. BSP finish
        out = np.empty(J)
        for j in range(J):
            finish = last[j] + dur[j]
            out[j] = q(finish - prev[j] if t > 0 else finish)
            prev[j] = release[j] = finish
            if pacers[j] is not None:
                # each rank waited from its arrival to the last one's,
                # and stepped from its own release to the finish
                for pacer, a, r in zip(pacers[j], arrival[j],
                                       rank_release[j]):
                    pacer.observe(last[j] - a, finish - r)
                rank_release[j] = [finish + p.decide() for p in pacers[j]]
        yield out


def series(scn: dict, quantize=None) -> np.ndarray:
    """The reported step series, ``(iters - warmup, jobs)``."""
    rows = list(steps(scn, quantize))
    return np.array(rows[scn["warmup"]:]).reshape(-1, len(scn["jobs"]))
