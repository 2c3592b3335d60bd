"""The link guarantees of a reconfigurable topology: the reference's audit on
hand-built timelines, and the log of matchings that the benchmark takes from
a serve (``probes.MatchingLog``)."""

import contextlib
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.chip import harness, reference
from benchmarks.chip import run as bench_run
from benchmarks.chip.probes import MatchingLog
from repro.online import cluster
from repro.online.workload import LinkEvent

# One job on 3 racks and 2 subchannels: task 0 on rack 0 over [0, 1), task 1
# on rack 1 over [2, 3), and one edge 0 -> 1 of size 2: 1 time unit over
# wireless at rate 2.
JOB = {0: (0.0, np.array([1.0, 1.0]), np.array([[0, 1]]), np.array([2.0]), 1.0, 2.0)}
RECORD = SimpleNamespace(
    job_id=0, arrival=0.0, admitted=0.0, completion=3.0, assignment=np.array([0, 1])
)
RACKS = [[(0.0, 1.0, 0)], [(2.0, 3.0, 0)], []]
RECONFIG = -2


def links(*entries):
    """A matching log from ``(time, [(rack, subchannel), ...])`` entries."""
    masks = np.zeros((len(entries), 3, 2), bool)
    for i, (_t, pairs) in enumerate(entries):
        for r, k in pairs:
            masks[i, r, k] = True
    return np.array([t for t, _ in entries], np.float64), masks


ON_K0 = [(0, 0), (1, 0)]
CASES = {
    "linked": (links((-np.inf, ON_K0)), [[(1.0, 2.0, 0)], []], (0, 0)),
    "no_topology": (None, [[], [(1.0, 2.0, 0)]], (0, 0)),
    "unlinked_subchannel": (links((-np.inf, ON_K0)), [[], [(1.0, 2.0, 0)]], (1, 0)),
    "link_dropped_mid_transfer": (
        links((-np.inf, ON_K0), (1.5, [(0, 0), (1, 1)])), [[(1.0, 2.0, 0)], []], (1, 0)
    ),
    "link_dropped_as_it_ends": (
        links((-np.inf, ON_K0), (2.0, [(0, 0), (1, 1)])), [[(1.0, 2.0, 0)], []], (0, 0)
    ),
    "linked_from_its_start": (
        links((-np.inf, []), (1.0, ON_K0)), [[(1.0, 2.0, 0)], []], (0, 0)
    ),
    "over_degree_before_any_transfer": (
        links((-np.inf, [(0, 0), (0, 1), (1, 0)]), (0.5, ON_K0)), [[(1.0, 2.0, 0)], []], (0, 0)
    ),
    "over_degree_under_a_transfer": (
        links((-np.inf, [(0, 0), (0, 1), (1, 0)])), [[(1.0, 2.0, 0)], []], (0, 1)
    ),
    "over_channel_degree_under_a_transfer": (
        links((-np.inf, [(0, 0), (1, 0), (2, 0)])), [[(1.0, 2.0, 0)], []], (0, 1)
    ),
    "reconfiguration_is_no_transfer": (
        links((-np.inf, ON_K0)), [[(1.0, 2.0, 0)], [(0.0, 1.0, RECONFIG)]], (0, 0)
    ),
    "one_edge_two_transfers": (
        links((-np.inf, ON_K0)), [[(1.0, 2.0, 0), (1.0, 2.0, 0)], []], (1, 0)
    ),
    "wrong_length": (None, [[(1.0, 1.5, 0)], []], (1, 0)),
    "before_its_source_ends": (None, [[(0.5, 1.5, 0)], []], (1, 0)),
    "unknown_owner": (None, [[(1.0, 2.0, 7)], []], (1, 0)),
}


@pytest.mark.parametrize("log, wireless, want", CASES.values(), ids=CASES.keys())
def test_audit_counts_transfers_off_their_links(log, wireless, want):
    """Counts ``(transfers_off_their_links, matchings_over_degree)`` at
    degree 1 and channel degree 2. An edge stands for one transfer at most,
    so of two that fit one edge, one is off its links."""
    got = reference.audit_serve(
        JOB, [RECORD], RACKS, [], wireless,
        links=log, degree=1, channel_degree=2, reconfig_id=RECONFIG,
    )
    assert (got["transfers_off_their_links"], got["matchings_over_degree"]) == want


def serve(tiny, config: str, log: MatchingLog | None = None, outages=(), policy=None):
    """The first stream of ``tiny_<config>.few``, served once, under an
    outage trace and another topology policy where given."""
    cfg = harness.config(f"tiny_{config}", tiny.root)
    if policy:
        cfg = dict(cfg, topology=dict(cfg["topology"], policy=policy))
    plan = bench_run.prepare(cfg, harness.traffic("few", tiny.root), tiny.seed)[0]
    svc = harness.scheduler(cfg, plan["engine_seed"])
    svc.outages = list(outages)
    with log or contextlib.nullcontext():
        return svc.serve(plan["events"])


def served(res) -> tuple:
    tl = res.timeline
    jobs = [(r.job_id, r.arrival, r.admitted, r.completion, tuple(r.assignment)) for r in res.jobs]
    return jobs, tl.rack_intervals, tl.wired_intervals, tl.wireless_intervals


@pytest.mark.parametrize("config", ["prod8", "topo8"])
def test_the_log_leaves_the_serve_as_it_was(config, tiny):
    """Bit for bit the same commits with and without the log; without a
    topology the log stays empty."""
    log = MatchingLog()
    res = serve(tiny, config, log)
    assert served(res) == served(serve(tiny, config))
    times, masks = log.of(res.timeline)
    if config == "prod8":
        assert times.size == 0 and masks.shape == (0, 8, 2)
    else:
        assert times[0] == -np.inf and np.all(masks[0]) and times.size > 2
        assert np.all(np.diff(times) > 0)


def test_the_log_keeps_each_change_of_the_usable_links(tiny, monkeypatch):
    """Under re-matching and an outage trace, the log holds the links each
    epoch leaves usable, whenever they changed: the state after every
    ``reconfigure`` (which follows the epoch's ``set_link`` calls), with
    consecutive repeats dropped."""
    after = []
    reconfigure, set_link = cluster.ClusterTimeline.reconfigure, cluster.ClusterTimeline.set_link
    flips = []

    def reconfigure_spy(self, weight, t):
        n = reconfigure(self, weight, t)
        after.append((t, self.matching & self.link_state))
        return n

    def set_link_spy(self, rack, k, up):
        changed = set_link(self, rack, k, up)
        flips.append(changed)
        return changed

    monkeypatch.setattr(cluster.ClusterTimeline, "reconfigure", reconfigure_spy)
    monkeypatch.setattr(cluster.ClusterTimeline, "set_link", set_link_spy)
    outages = [LinkEvent(100.0, 0, 0, False), LinkEvent(100.0, 1, 1, False),
               LinkEvent(250.0, 0, 0, True)]
    log = MatchingLog()
    res = serve(tiny, "topo8", log, outages)
    assert flips == [True, True, True] and res.n_link_events == 3

    want = [(-np.inf, np.ones((8, 2), bool))]
    for t, mask in after:
        if not np.array_equal(mask, want[-1][1]):
            want.append((t, mask))
    times, masks = log.of(res.timeline)
    assert times.tolist() == [t for t, _ in want]
    assert np.array_equal(masks, np.array([m for _, m in want]))
    assert (times >= 100.0).any() and not masks[times >= 100.0][:, 1, 1].any()


def test_static_links_log_each_outage(tiny):
    """Without re-matching, the links move only by the outage trace: down at
    the first epoch at or after 100, up again at the first at or after 250."""
    log = MatchingLog()
    outages = [LinkEvent(100.0, 3, 1, False), LinkEvent(250.0, 3, 1, True)]
    res = serve(tiny, "topo8", log, outages, policy="static")
    times, masks = log.of(res.timeline)
    down = np.ones((8, 2), bool)
    down[3, 1] = False
    assert times[0] == -np.inf and 100.0 <= times[1] < 250.0 <= times[2]
    assert np.array_equal(masks, np.stack([np.ones((8, 2), bool), down, np.ones((8, 2), bool)]))
    assert res.n_reconfigs == 0
