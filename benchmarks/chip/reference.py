"""Plain reference of the scheduler's answers, independent of the program.

It imports nothing of ``repro``. It reads a job as the question it is (task
durations, edges, data sizes, the racks and subchannels a view grants, the
reach mask) and answers in straightforward NumPy:

- :func:`stage1_bound`: the combined §IV-A stage-1 lower bound of a
  candidate rack assignment, as the engine defines it: the longest path of
  the DAG where an edge costs its source task plus a local delay (same rack)
  or the fastest network transfer (cross rack; the wired one when the two
  racks share no reachable subchannel), closed by the sink task, maxed with
  the busiest rack's work, the cross-rack transfer work over the ``1 + |K|``
  network channels, and the serial work of the edges forced onto wire.
- :func:`stage2_makespan`: the stage-2 score, a non-delay greedy pass over
  the operations in a fixed topological order (each edge just before its
  destination task), appending each to its rack or to the channel that
  finishes it first (wired first on ties).
- :func:`audit_serve`: the committed timeline as a set of guarantees: every
  job served once, no two operations overlapping on one rack or channel,
  each task on its rack for its duration after its predecessors and their
  transfers; each wireless transfer one cross edge of its job, between its
  tasks, on a subchannel that both its racks are linked to by every
  matching in force while it runs; and no such matching linking a rack to
  more than ``degree`` subchannels or a subchannel to more than
  ``channel_degree`` racks.

``dtype`` is the precision of the arithmetic: float32 as the configuration
states it, or a lower one for the control.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "Question",
    "stage1_bound",
    "stage2_makespan",
    "rel_gap",
    "audit_serve",
]


@dataclasses.dataclass(frozen=True)
class Question:
    """One job on one granted view: what stage 1 and stage 2 are asked."""

    p: np.ndarray  # float64[n] task durations
    edges: np.ndarray  # int64[m, 2]
    d: np.ndarray  # float64[m] data sizes
    n_racks: int  # racks granted
    n_wireless: int  # subchannels granted
    wired_rate: float
    wireless_rate: float
    local_delay: float
    reach: np.ndarray | None  # bool[n_racks, n_wireless], None = all reach

    @classmethod
    def of(cls, inst) -> "Question":
        """Read the plain fields of a program instance (no derived tables)."""
        job = inst.job
        topo = inst.topology
        return cls(
            p=np.asarray(job.p, np.float64),
            edges=np.asarray(job.edges, np.int64).reshape(-1, 2),
            d=np.asarray(job.d, np.float64),
            n_racks=int(inst.n_racks),
            n_wireless=int(inst.n_wireless),
            wired_rate=float(inst.wired_rate),
            wireless_rate=float(inst.wireless_rate),
            local_delay=float(np.asarray(inst.local_delay)),
            reach=None if topo is None else np.asarray(topo.reach, bool),
        )


def topo_order(n: int, edges: np.ndarray) -> list[int]:
    """Kahn's order: the lowest-numbered ready task first, then the tasks each
    one frees, most recently freed first."""
    indeg = np.zeros(n, np.int64)
    out: dict[int, list[int]] = {}
    for u, v in edges:
        indeg[v] += 1
        out.setdefault(int(u), []).append(int(v))
    stack = sorted(np.nonzero(indeg == 0)[0].tolist(), reverse=True)
    order = []
    while stack:
        u = stack.pop()
        order.append(u)
        for v in out.get(u, ()):
            indeg[v] -= 1
            if indeg[v] == 0:
                stack.append(v)
    return order


def _cross_ok(q: Question, ru: np.ndarray, rv: np.ndarray) -> np.ndarray:
    """bool[B]: the racks of an edge's endpoints share a reachable subchannel
    (always true without a reach mask)."""
    if q.reach is None:
        return np.ones(ru.shape, bool)
    return (q.reach[ru] & q.reach[rv]).any(axis=-1)


def stage1_bound(q: Question, racks: np.ndarray, dtype=np.float32) -> np.ndarray:
    """The combined stage-1 bound of each row of ``racks`` (int[B, >= n])."""
    f = np.dtype(dtype).type
    n = q.p.shape[0]
    racks = np.asarray(racks)[:, :n].astype(np.int64)
    B = racks.shape[0]
    p = q.p.astype(dtype)
    wired = (q.d / q.wired_rate).astype(dtype)
    fastest = (
        np.minimum(q.d / q.wired_rate, q.d / q.wireless_rate) if q.n_wireless
        else q.d / q.wired_rate
    ).astype(dtype)
    local = f(q.local_delay)
    dist = np.zeros((B, n), dtype)
    load = np.zeros((B, q.n_racks), dtype)
    work = np.zeros(B, dtype)
    forced_work = np.zeros(B, dtype)
    in_edges: dict[int, list[int]] = {}
    for e, (_u, v) in enumerate(q.edges):
        in_edges.setdefault(int(v), []).append(e)
    for v in topo_order(n, q.edges):
        for e in in_edges.get(v, ()):
            u = int(q.edges[e, 0])
            cross = racks[:, u] != racks[:, v]
            forced = cross & ~_cross_ok(q, racks[:, u], racks[:, v])
            net = np.where(forced, wired[e], fastest[e])
            cost = np.where(cross, net, local) + p[u]
            dist[:, v] = np.maximum(dist[:, v], dist[:, u] + cost)
            work += np.where(cross, net, f(0))
            forced_work += np.where(forced, wired[e], f(0))
    for v in range(n):
        load[np.arange(B), racks[:, v]] += p[v]
    path = (dist + p).max(axis=1) if n else np.zeros(B, dtype)
    share = work / f(1 + q.n_wireless)
    return np.maximum(np.maximum(path, load.max(axis=1)), np.maximum(share, forced_work))


def stage2_makespan(q: Question, racks: np.ndarray, dtype=np.float32) -> np.ndarray:
    """The greedy stage-2 makespan of each row of ``racks`` (int[B, >= n])."""
    f = np.dtype(dtype).type
    n = q.p.shape[0]
    racks = np.asarray(racks)[:, :n].astype(np.int64)
    B = racks.shape[0]
    rows = np.arange(B)
    p = q.p.astype(dtype)
    wired = (q.d / q.wired_rate).astype(dtype)
    wireless = (q.d / q.wireless_rate).astype(dtype)
    local = f(q.local_delay)
    rack_free = np.zeros((B, q.n_racks), dtype)
    chan_free = np.zeros((B, 1 + q.n_wireless), dtype)
    task_fin = np.zeros((B, n), dtype)
    edge_fin = np.zeros((B, q.edges.shape[0]), dtype)
    in_edges: dict[int, list[int]] = {}
    for e, (_u, v) in enumerate(q.edges):
        in_edges.setdefault(int(v), []).append(e)
    for v in topo_order(n, q.edges):
        ready = np.zeros(B, dtype)
        for e in in_edges.get(v, ()):
            u = int(q.edges[e, 0])
            start_u = task_fin[:, u]
            best = np.maximum(start_u, chan_free[:, 0]) + wired[e]
            pick = np.zeros(B, np.int64)
            for k in range(q.n_wireless):
                fin = np.maximum(start_u, chan_free[:, 1 + k]) + wireless[e]
                if q.reach is not None:
                    ok = q.reach[racks[:, u], k] & q.reach[racks[:, v], k]
                    fin = np.where(ok, fin, f(np.inf))
                better = fin < best
                best = np.where(better, fin, best)
                pick = np.where(better, 1 + k, pick)
            same = racks[:, u] == racks[:, v]
            cross = rows[~same]
            chan_free[cross, pick[~same]] = best[~same]
            edge_fin[:, e] = np.where(same, start_u + local, best)
            ready = np.maximum(ready, edge_fin[:, e])
        fin = np.maximum(ready, rack_free[rows, racks[:, v]]) + p[v]
        rack_free[rows, racks[:, v]] = fin
        task_fin[:, v] = fin
    return task_fin.max(axis=1) if n else np.zeros(B, dtype)


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest gap between two answers, relative to the reference's size."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0), initial=0.0))


def _overlaps(intervals, tol: float) -> int:
    ivs = sorted((float(s), float(e)) for s, e, *_ in intervals)
    return sum(1 for (_s0, e0), (s1, _e1) in zip(ivs, ivs[1:]) if s1 < e0 - tol)


def _in_force(times: np.ndarray, s: float, e: float, tol: float) -> range | None:
    """Indices of the matchings in force over ``[s, e)``: the one in force at
    ``s`` and each later one that starts before ``e`` (``None`` where none is
    in force at ``s``). Entry ``i`` holds from ``times[i]`` to ``times[i + 1]``."""
    pad = tol * max(1.0, abs(e))
    lo = int(np.searchsorted(times, s + pad, side="right")) - 1
    hi = int(np.searchsorted(times, e - pad, side="left"))
    return None if lo < 0 else range(lo, max(hi, lo + 1))


def _most_matched(fits: list[list[int]]) -> int:
    """Size of a largest matching of intervals to edges: ``fits[i]`` lists the
    edges interval ``i`` may stand for, and each edge stands for one at most."""
    owner: dict[int, int] = {}

    def take(i: int, seen: set) -> bool:
        for e in fits[i]:
            if e not in seen:
                seen.add(e)
                if e not in owner or take(owner[e], seen):
                    owner[e] = i
                    return True
        return False

    return sum(take(i, set()) for i in range(len(fits)))


def audit_serve(
    jobs: dict,
    records: list,
    rack_intervals: list,
    wired_intervals: list,
    wireless_intervals: list,
    links: tuple | None = None,
    degree: int | None = None,
    channel_degree: int | None = None,
    reconfig_id: int | None = None,
    tol: float = 1e-6,
) -> dict[str, int]:
    """Counts of broken guarantees in one committed serve (all 0 when sound).

    ``jobs`` maps job id to ``(arrival, p, edges, d, wired_rate,
    wireless_rate)``; ``records`` are the served jobs' records (``job_id``,
    ``arrival``, ``admitted``, ``completion``, ``assignment``); the interval
    lists are the committed ``(start, end, job_id)`` triples per rack, of the
    wired channel, and per wireless subchannel.

    ``links`` is the log of usable wireless links, ``(times float[L], masks
    bool[L, n_racks, n_wireless])``: mask ``i`` is in force from ``times[i]``
    until ``times[i + 1]``. ``None`` means no topology: every rack reaches
    every subchannel and no degree holds. Wireless intervals owned by
    ``reconfig_id`` are reconfiguration delays, not transfers.
    """
    ids = [int(r.job_id) for r in records]
    served = sorted(set(ids))
    missing_or_twice = len(set(jobs) - set(served)) + (len(ids) - len(served))
    missing_or_twice += len(set(served) - set(jobs))
    overlaps = sum(_overlaps(ivs, tol) for ivs in rack_intervals)
    overlaps += _overlaps(wired_intervals, tol)
    overlaps += sum(_overlaps(ivs, tol) for ivs in wireless_intervals)

    on_rack: dict[int, list] = {}
    for rack, ivs in enumerate(rack_intervals):
        for s, e, j in ivs:
            on_rack.setdefault(int(j), []).append((rack, float(s), float(e)))
    transfers: dict[int, int] = {}
    for _s, _e, j in [iv for ivs in [wired_intervals, *wireless_intervals] for iv in ivs]:
        transfers[int(j)] = transfers.get(int(j), 0) + 1
    placed: dict[int, tuple] = {}  # job id -> (assignment, task starts)
    broken = 0
    for r in records:
        j = int(r.job_id)
        if j not in jobs:
            continue
        arrival, p, edges, d, wired_rate, wireless_rate = jobs[j]
        mine = sorted(on_rack.get(j, []))
        assign = np.asarray(r.assignment)
        n_cross = int(np.sum(assign[edges[:, 0]] != assign[edges[:, 1]])) if len(edges) else 0
        ok = len(mine) == p.shape[0] and r.admitted >= arrival - tol
        start = np.full(p.shape[0], np.nan)
        placed[j] = (assign, start)
        for v in range(p.shape[0]) if ok else ():
            rack = int(assign[v])
            fits = [
                k for k, (rk, s, e) in enumerate(mine)
                if rk == rack and abs((e - s) - p[v]) <= tol * max(1.0, p[v])
            ]
            if not fits:
                ok = False
                break
            _rk, s, e = mine.pop(fits[0])
            start[v] = s
            ok &= r.admitted - tol <= s and e <= r.completion + tol
        ok &= transfers.get(j, 0) == n_cross
        if ok:
            end = start + p
            for (u, v), size in zip(edges, d):
                gap = start[v] - end[u]
                if assign[u] != assign[v]:
                    need = min(size / wired_rate, size / wireless_rate)
                    ok &= gap >= need - tol * max(1.0, need)
                else:
                    ok &= gap >= -tol
            ok &= abs(float(np.max(end)) - r.completion) <= tol * max(1.0, r.completion)
        broken += not ok

    wireless_of: dict[int, list] = {}
    for k, ivs in enumerate(wireless_intervals):
        for s, e, j in ivs:
            if int(j) != reconfig_id:
                wireless_of.setdefault(int(j), []).append((k, float(s), float(e)))

    def linked(ra: int, rb: int, k: int, s: float, e: float) -> bool:
        if links is None:
            return True
        force = _in_force(links[0], s, e, tol)
        return force is not None and bool(np.all(links[1][force, ra, k] & links[1][force, rb, k]))

    off_links = 0
    for j, ivs in wireless_of.items():
        fits: list[list[int]] = [[] for _ in ivs]
        if j in placed:
            _arrival, p, edges, d, _wired_rate, wireless_rate = jobs[j]
            assign, start = placed[j]
            end = start + p
            for i, (k, s, e) in enumerate(ivs):
                for n, ((u, v), size) in enumerate(zip(edges, d)):
                    ra, rb, need = int(assign[u]), int(assign[v]), size / wireless_rate
                    if (
                        ra != rb
                        and abs((e - s) - need) <= tol * max(1.0, need)
                        and s >= end[u] - tol * max(1.0, abs(end[u]))
                        and e <= start[v] + tol * max(1.0, abs(start[v]))
                        and linked(ra, rb, k, s, e)
                    ):
                        fits[i].append(n)
        off_links += len(ivs) - _most_matched(fits)

    over_degree = 0
    if links is not None:
        times, masks = links
        over = np.zeros(len(times), bool)
        if degree is not None:
            over |= (masks.sum(axis=2) > degree).any(axis=1)
        if channel_degree is not None:
            over |= (masks.sum(axis=1) > channel_degree).any(axis=1)
        used: set[int] = set()
        for ivs in wireless_of.values():
            for _k, s, e in ivs:
                used.update(_in_force(times, s, e, tol) or ())
        over_degree = int(sum(over[i] for i in used))
    return {
        "jobs_missing_or_twice": missing_or_twice,
        "overlaps": overlaps,
        "jobs_off_their_dag": broken,
        "transfers_off_their_links": off_links,
        "matchings_over_degree": over_degree,
    }
