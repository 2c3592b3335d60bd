"""JAX-vectorized schedule search (beyond-paper, TPU-native).

The paper's solver is host-side B&B. On TPU-class hardware the natural
adaptation of its *search* is massive data parallelism: evaluate tens of
thousands of candidate rack assignments simultaneously as one batched tensor
program. This module implements that search as a two-stage, device-sharded
batch engine whose padding and masking are **instance-aware end-to-end**: a
fleet of heterogeneous :class:`ProblemInstance`\\ s is packed into one padded
mega-batch (shared size bucket, per-row instance ids, per-instance channel
masks) and solved by a single pair of compiled programs.

  Stage 1 (bound): every candidate passes through the paper's combined
  §IV-A lower bound, computed batched on-device by the fused Pallas kernel
  :func:`repro.kernels.ops.batched_combined_lb` — the critical-path bound
  (iterated max-plus relaxation on dense adjacency blocks) maxed with the
  contention terms (per-rack work, aggregate wired+wireless channel work;
  see :mod:`repro.core.bounds` for the §IV-A term-to-array mapping).
  Candidates whose bound already meets the running incumbent are discarded
  without ever being scheduled; the contention terms are what let dense
  instances (where the contention-free critical path prunes 0%) prune.

  Stage 2 (evaluate): survivors are scored by a greedy non-delay schedule
  executed in lock-step across the batch. The evaluator is a single
  ``lax.scan`` over *static op tables* in the shared layout of
  :func:`repro.core.simulator.pad_op_tables` — per-instance tables are
  stacked on a leading axis and gathered per batch row by instance id, so
  candidates of **different** jobs ride in the same launch, and one
  compiled program serves every fleet whose size bucket matches. Those
  gathers run once per launch, before the loop; the scan step holds none.
  What depends on the candidate's racks but not on the carry (each task's
  rack, each edge's endpoint racks, the channels both endpoints reach) is
  computed before the loop as masked maxima, and the step reads its carry
  through exact one-hot selects (``max(where(one_hot, table, fill))`` over
  at most 33 entries). Everything the step touches is laid out
  ``[..., W, B]``, batch rows on the TPU's lanes, so a select is a few
  vector ops across sublanes where a per-row gather costs far more. Batches
  are sharded across local devices with ``shard_map`` when more than one
  device is present, degrading gracefully to a plain ``jit``.

Fleet API: :func:`schedule_fleet` runs N heterogeneous instances through
the lockstep driver — per-instance incumbents, pruning and refinement
evolve exactly as in the single-instance :func:`vectorized_search` (which
is now the fleet-of-one special case), so each per-instance result is
bit-for-bit identical to solving that instance alone, while the fleet pays
one sharded launch (and at most one trace) per stage instead of one per
instance. :class:`FleetResult` reports per-instance results plus fleet
prune / launch / trace counters.

  Refinement (sampled regime): the incumbent stream feeds the strategy
  portfolio of :mod:`repro.core.portfolio` — mutation local search by
  default (bit-for-bit the pre-portfolio loop), optionally elite
  crossover and simulated annealing with a multiplicative-weights budget
  allocator (``strategies="portfolio"``). All strategies' proposals ride
  the same lockstep launches and the same stage-1 pruner; per-strategy
  counters surface as ``strategy_stats`` on the results.

This module is an *incumbent generator / pruner*: the winning assignment is
re-executed exactly with the host simulator and verified by the OP checker.
Exactness guarantees come from `bnb`/`solver_milp`; tests assert the
vectorized score is always >= the exact optimum and == the simulator's
makespan for the reconstructed schedule. Pruning is exact with respect to
the greedy objective: greedy(c) >= LB(c), so LB(c) >= incumbent implies c
cannot improve the incumbent.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bounds as bounds_mod
from repro.core import portfolio as portfolio_mod
from repro.core.instance import ProblemInstance
from repro.core.schedule import Schedule
from repro.core.simulator import OP_EDGE, OP_TASK, build_op_tables, pad_op_tables, simulate
from repro.obs.trace import as_tracer

__all__ = [
    "enumerate_assignments",
    "sample_assignments",
    "make_batched_evaluator",
    "batched_lower_bound",
    "vectorized_search",
    "schedule_fleet",
    "VectorizedResult",
    "FleetResult",
]


@functools.lru_cache(maxsize=256)
def enumerate_assignments(n: int, max_racks: int, limit: int | None = None) -> np.ndarray:
    """All canonical task->rack assignments (restricted growth strings).

    Canonical = rack labels appear in first-use order, which quotients out
    rack-relabelling symmetry. Returns int32[count, n].

    The result depends only on the arguments, so it is built once per
    process and shared by every later call with the same arguments; it is
    read-only, and a caller that needs to write takes a copy.
    """
    out: list[list[int]] = []

    def rec(prefix: list[int], n_used: int) -> None:
        if limit is not None and len(out) >= limit:
            return
        if len(prefix) == n:
            out.append(list(prefix))
            return
        for i in range(min(n_used + 1, max_racks)):
            prefix.append(i)
            rec(prefix, max(n_used, i + 1))
            prefix.pop()
            if limit is not None and len(out) >= limit:
                return

    rec([], 0)
    rows = np.asarray(out, dtype=np.int32).reshape(-1, n)
    rows.setflags(write=False)
    return rows


def sample_assignments(
    rng: np.random.Generator, n: int, max_racks: int, count: int
) -> np.ndarray:
    """Random assignments (not canonicalized; used when enumeration is big)."""
    return rng.integers(0, max_racks, size=(count, n), dtype=np.int32).astype(np.int32)


# ---------------------------------------------------------------------------
# Size buckets
# ---------------------------------------------------------------------------

def _bucket(x: int, lo: int = 8) -> int:
    """Smallest power of two >= max(x, lo): the size-bucket rounding used for
    every padded dimension so compiled programs are shared across fleets."""
    b = lo
    while b < x:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class _FleetDims:
    """Shared size bucket of a (possibly heterogeneous) instance fleet.

    Every padded dimension is the bucket of the fleet-wide maximum, so all
    instances share one op-table layout and one compiled program per stage.
    ``n_iters`` is the true relaxation depth bound (max task count - 1):
    extra rounds past an instance's own depth are exact no-ops, which keeps
    per-instance bounds bit-identical under any fleet padding.
    """

    n_ops: int
    n_pad: int
    m_pad: int
    M_pad: int
    indeg_pad: int
    n_chan: int
    n_iters: int


def _fleet_dims(instances, op_tables=None) -> _FleetDims:
    """Size bucket of a fleet. ``op_tables`` (one prebuilt ``OpTables`` per
    instance) sizes the evaluator dims; LB-only callers omit it and must
    not read ``n_ops`` / ``indeg_pad`` (they stay at the bucket floor).
    The channel count is one wired channel plus the fleet's most wireless
    subchannels (at least one)."""
    n_ops = n = m = M = indeg = wireless = 1
    for i, inst in enumerate(instances):
        if op_tables is not None:
            n_ops = max(n_ops, op_tables[i].n_ops)
            indeg = max(indeg, op_tables[i].task_in_edges.shape[1])
        n = max(n, inst.job.n_tasks)
        m = max(m, inst.job.n_edges)
        M = max(M, inst.n_racks)
        wireless = max(wireless, inst.n_wireless)
    return _FleetDims(
        n_ops=_bucket(n_ops),
        n_pad=_bucket(n),
        m_pad=_bucket(m),
        M_pad=_bucket(M, lo=2),
        indeg_pad=_bucket(indeg, lo=4),
        n_chan=1 + wireless,
        n_iters=max(0, n - 1),
    )


# ---------------------------------------------------------------------------
# Stage-2 evaluator: instance-aware op-table lax.scan program
# ---------------------------------------------------------------------------

# Incremented each time the scan evaluator is traced; lets tests assert that
# fleets sharing a size bucket reuse the compiled program.
TRACE_COUNT = 0

# Same, for the stage-1 combined-bound program.
LB_TRACE_COUNT = 0


def _scan_evaluate(
    rack,       # int32[B, n_pad]  candidate assignments (one job's tasks per row)
    inst_id,    # int32[B]         which fleet instance each row belongs to
    kind,       # int32[I, n_ops]  OP_TASK / OP_EDGE / OP_PAD
    op_task,    # int32[I, n_ops]  task id for OP_TASK rows (0 otherwise)
    op_edge,    # int32[I, n_ops]  edge id for OP_EDGE rows (0 otherwise)
    op_src,     # int32[I, n_ops]  edge source task (0 otherwise)
    op_dst,     # int32[I, n_ops]  edge dest task (0 otherwise)
    op_p,       # f32[I, n_ops]    task duration
    op_wired,   # f32[I, n_ops]    wired transfer duration
    op_wireless,  # f32[I, n_ops]  wireless transfer duration
    op_local,   # f32[I, n_ops]    local transfer delay
    op_in,      # int32[I, n_ops, indeg_pad] in-edge ids gating a task row;
                #                  the sentinel id m_pad always reads 0.0
    chan_free0,  # f32[I, n_chan]  initial channel availability: 0 = usable,
                #                  +inf = masked (instance has fewer channels)
    reach,      # f32[I, M_pad, n_chan] topology reachability: 1 = rack may
                #                  use the channel (col 0, wired, always 1);
                #                  all-ones when the instance has no topology
    *,
    m_pad: int,
    M_pad: int,
    n_chan: int,
):
    global TRACE_COUNT
    TRACE_COUNT += 1
    B, n_pad = rack.shape

    def take(t):
        return jnp.take(t, inst_id, axis=0)

    # Batch rows lie on the minor (lane) axis: every array the step reads is
    # [..., W, B], so a select reduces across sublanes with all lanes busy.
    def hot(idx, width):  # ids [..., B] -> one-hot [..., width, B]
        return idx[..., None, :] == jnp.arange(width, dtype=idx.dtype)[:, None]

    def select(tab, h, fill):  # tab [..., W, B], one-hot h -> tab[h], [..., B]
        return jnp.max(jnp.where(h, tab, fill), axis=-2)

    # Lookups that depend on the candidate's racks and the op tables but not
    # on the carry, once per launch as [n_ops, ..., B]: the task's rack, the
    # edge endpoints' racks, and the channels both endpoints reach.
    rack_t = rack.T  # [n_pad, B]
    reach_t = jnp.moveaxis(take(reach), 0, -1)  # [M_pad, n_chan, B]

    def rack_of(task):  # task ids [n_ops, B] -> racks [n_ops, B]
        return select(rack_t, hot(task, n_pad), 0)

    def reach_of(r):  # rack ids [n_ops, B] -> reach rows [n_ops, n_chan, B]
        return jnp.max(jnp.where(hot(r, M_pad)[:, :, None], reach_t, 0.0), axis=1)

    rv = rack_of(take(op_task).T)
    ru, rw = rack_of(take(op_src).T), rack_of(take(op_dst).T)
    # Topology gating: a channel is usable iff both endpoint racks reach it
    # (row 0, wired, is always reachable).
    feas = reach_of(ru) * reach_of(rw) > 0
    # Multi-hot of each task row's gating in-edges over the edge-finish
    # table (the sentinel row m_pad always reads 0.0).
    in_mask = jnp.any(jax.nn.one_hot(op_in, m_pad + 1, dtype=bool), axis=2)

    # Per-row tables, scan axis leading. Rows of different instances walk
    # different op sequences in lock-step; OP_PAD rows are no-ops.
    xs = (
        take(kind).T, take(op_task).T, take(op_edge).T, take(op_src).T,
        take(op_p).T, take(op_wired).T, take(op_wireless).T, take(op_local).T,
        jnp.moveaxis(take(in_mask), 0, -1), rv, ru == rw, feas,
    )
    carry0 = (
        jnp.zeros((M_pad, B), jnp.float32),      # rack_free
        take(chan_free0).T,                      # chan_free (+inf = masked)
        jnp.zeros((n_pad, B), jnp.float32),      # task_fin
        jnp.zeros((m_pad + 1, B), jnp.float32),  # edge_fin (+1 sentinel row)
    )

    # The step reads the carry through exact one-hot selects (every finish
    # time is >= 0), so its body holds no gather.
    def step(carry, x):
        rack_free, chan_free, task_fin, edge_fin = carry
        kind_t, t_v, e_id, u, p_v, q_w, q_wl, r_l, in_row, rv, same, feas = x
        is_task = kind_t == OP_TASK
        is_edge = kind_t == OP_EDGE

        # Task branch (reads the pre-step carry): start when all gating
        # in-edges have finished and the task's rack is free.
        ready_t = select(edge_fin, in_row, 0.0)
        rv_hot = hot(rv, M_pad)
        fin_t = jnp.maximum(ready_t, select(rack_free, rv_hot, -jnp.inf)) + p_v

        # Edge branch (reads the pre-step carry; a row is task OR edge at
        # any step, so both branches can share it).
        ready_e = select(task_fin, hot(u, n_pad), -jnp.inf)
        fin_local = ready_e + r_l
        # Network path: earliest-finish channel (0 wired, 1.. wireless);
        # masked and unreachable channels sit at +inf and are never selected.
        durs = jnp.concatenate(
            [q_w[None], jnp.broadcast_to(q_wl[None], (n_chan - 1, B))], axis=0
        )
        s = jnp.maximum(ready_e[None], chan_free)
        f = jnp.where(feas, s + durs, jnp.inf)
        best = jnp.argmin(f, axis=0)
        fin_net = jnp.min(f, axis=0)
        new_free = jnp.where(hot(best, n_chan), fin_net[None], chan_free)
        fin_e = jnp.where(same, fin_local, fin_net)

        # Merge by per-row op kind (OP_PAD rows change nothing).
        rack_free = jnp.where(is_task[None] & rv_hot, fin_t[None], rack_free)
        task_fin = jnp.where(is_task[None] & hot(t_v, n_pad), fin_t[None], task_fin)
        chan_free = jnp.where((is_edge & ~same)[None], new_free, chan_free)
        edge_fin = jnp.where(
            is_edge[None] & hot(e_id, m_pad + 1), fin_e[None], edge_fin
        )
        return (rack_free, chan_free, task_fin, edge_fin), None

    (_, _, task_fin, _), _ = jax.lax.scan(step, carry0, xs)
    return jnp.max(task_fin, axis=0)


@functools.lru_cache(maxsize=None)
def _compiled_evaluator(n_dev: int, m_pad: int, M_pad: int, n_chan: int):
    """Jitted (and, with >1 local device, shard_map-sharded) scan evaluator.

    The returned callable is cached per (device count, static dims); jit then
    caches per concrete table/batch shape — so any two fleets in the same
    size bucket share one compiled program.
    """
    core = functools.partial(
        _scan_evaluate, m_pad=m_pad, M_pad=M_pad, n_chan=n_chan
    )
    if n_dev <= 1:
        return jax.jit(core)

    from jax.sharding import Mesh, PartitionSpec as P

    # Local devices only: batch padding by the callers is sized to divide by
    # local_device_count, and each process shards its own host-local batch.
    # Only the candidate rows are sharded; tables are replicated.
    mesh = Mesh(np.asarray(jax.local_devices()), ("b",))
    r2, r3 = P(None, None), P(None, None, None)
    sharded = jax.shard_map(
        core,
        mesh=mesh,
        in_specs=(P("b", None), P("b"), r2, r2, r2, r2, r2, r2, r2, r2, r2,
                  r3, r2, r3),
        out_specs=P("b"),
        check_vma=False,
    )
    return jax.jit(sharded)


def _build_eval_stack(instances, dims: _FleetDims, op_tables=None):
    """Stacked device op tables [I, ...] in ``_scan_evaluate`` order. An
    instance's channels past its own ``1 + n_wireless`` stay masked."""
    I = len(instances)
    fields = {
        "kind": np.zeros((I, dims.n_ops), np.int32),
        "op_task": np.zeros((I, dims.n_ops), np.int32),
        "op_edge": np.zeros((I, dims.n_ops), np.int32),
        "op_src": np.zeros((I, dims.n_ops), np.int32),
        "op_dst": np.zeros((I, dims.n_ops), np.int32),
        "op_p": np.zeros((I, dims.n_ops), np.float32),
        "op_wired": np.zeros((I, dims.n_ops), np.float32),
        "op_wireless": np.zeros((I, dims.n_ops), np.float32),
        "op_local": np.zeros((I, dims.n_ops), np.float32),
        "op_in": np.zeros((I, dims.n_ops, dims.indeg_pad), np.int32),
    }
    chan_free0 = np.full((I, dims.n_chan), np.inf, np.float32)
    reach = np.ones((I, dims.M_pad, dims.n_chan), np.float32)
    for i, inst in enumerate(instances):
        t = pad_op_tables(
            inst,
            n_ops=dims.n_ops,
            indeg_pad=dims.indeg_pad,
            edge_sentinel=dims.m_pad,
            tables=None if op_tables is None else op_tables[i],
        )
        for name in fields:
            fields[name][i] = getattr(t, name)
        n_ch = 1 + inst.n_wireless
        chan_free0[i, :n_ch] = 0.0
        if inst.topology is not None and n_ch > 1:
            reach[i, : inst.n_racks, 1:n_ch] = inst.topology.reach
    return tuple(jnp.asarray(fields[name]) for name in fields) + (
        jnp.asarray(chan_free0),
        jnp.asarray(reach),
    )


def make_batched_evaluator(inst: ProblemInstance):
    """Build a fn: rack[B, n] int -> makespan[B] float32 (greedy non-delay).

    The fleet-of-one special case of the mega-batch evaluator: pads its
    batch to the instance's size bucket (batch to a power of two times the
    local device count) and dispatches the shared compiled scan program —
    identical instances never retrace, and instances of similar size share
    one compiled program per bucket.
    """
    ops = [build_op_tables(inst)]
    dims = _fleet_dims([inst], ops)
    tables = _build_eval_stack([inst], dims, ops)
    n = inst.job.n_tasks
    n_dev = jax.local_device_count()
    fn = _compiled_evaluator(n_dev, dims.m_pad, dims.M_pad, dims.n_chan)

    def evaluate(rack) -> jax.Array:
        rack = np.asarray(rack, dtype=np.int32)
        B = rack.shape[0]
        B_pad = _bucket(B) * (n_dev if _bucket(B) % n_dev else 1)
        padded = np.zeros((B_pad, dims.n_pad), dtype=np.int32)
        padded[:B, :n] = rack
        inst_id = np.zeros(B_pad, dtype=np.int32)
        return fn(jnp.asarray(padded), jnp.asarray(inst_id), *tables)[:B]

    evaluate.dims = dims
    return evaluate


# ---------------------------------------------------------------------------
# Stage-1 bound: fused Pallas combined §IV-A bound over the mega-batch
# ---------------------------------------------------------------------------

def _build_lb_arrays(instances, dims: _FleetDims):
    """Stacked stage-1 arrays [I, ...] for ``_fleet_lb_device``.

    Padded edges carry -inf costs (their scatter into the max-plus adjacency
    is a no-op) and zero ``net_work`` (they add nothing to the aggregate
    channel-work term); padded tasks carry zero duration.

    When any instance carries a :class:`~repro.core.instance.Topology`, two
    extra arrays feed the matching-feasibility mask of the fused kernel:
    ``pair_ok[I, M_pad, M_pad]`` (1 = the rack pair shares at least one
    reachable subchannel; all-ones for topology-free instances) and
    ``uplift[I, m_pad]`` (the forced-wired uplift ``q - min(q, q̌)`` per
    edge, 0 on padding). Topology-free fleets omit them, so the compiled
    stage-1 program is byte-for-byte the pre-topology one.
    """
    I = len(instances)
    src = np.zeros((I, dims.m_pad), np.int32)
    dst = np.zeros((I, dims.m_pad), np.int32)
    p_src = np.zeros((I, dims.m_pad), np.float32)
    c_local = np.full((I, dims.m_pad), -np.inf, np.float32)
    c_net = np.full((I, dims.m_pad), -np.inf, np.float32)
    net_work = np.zeros((I, dims.m_pad), np.float32)
    p_task = np.zeros((I, dims.n_pad), np.float32)
    chan_div = np.ones(I, np.float32)
    topo_on = any(inst.topology is not None for inst in instances)
    pair_ok = np.ones((I, dims.M_pad, dims.M_pad), np.float32) if topo_on else None
    uplift = np.zeros((I, dims.m_pad), np.float32) if topo_on else None
    for i, inst in enumerate(instances):
        job = inst.job
        m = job.n_edges
        p_task[i, : job.n_tasks] = job.p
        chan_div[i] = 1 + inst.n_wireless
        if m:
            src[i, :m] = job.edges[:, 0]
            dst[i, :m] = job.edges[:, 1]
            p_src[i, :m] = job.p[job.edges[:, 0]]
            c_local[i, :m] = inst.r_local
            net = bounds_mod.min_network_durations(inst)
            c_net[i, :m] = net
            net_work[i, :m] = net
            if topo_on:
                uplift[i, :m] = np.asarray(inst.q_wired, np.float32) - net
        if topo_on and inst.topology is not None:
            M = inst.n_racks
            pair_ok[i, :M, :M] = inst.topology.pair_connected()
    out = (src, dst, p_src, c_local, c_net, net_work, p_task, chan_div)
    if topo_on:
        out = out + (pair_ok, uplift)
    return tuple(jnp.asarray(a) for a in out)


@functools.partial(jax.jit, static_argnames=("M_pad", "n_iters"))
def _fleet_lb_device(
    racks,      # int32[B, n_pad]
    inst_id,    # int32[B]
    src,        # int32[I, m_pad]
    dst,        # int32[I, m_pad]
    p_src,      # f32[I, m_pad]  source-task duration per edge (0 on padding)
    c_local,    # f32[I, m_pad]  local delay per edge (-inf on padding)
    c_net,      # f32[I, m_pad]  optimistic network duration (-inf on padding)
    net_work,   # f32[I, m_pad]  min network duration (0 on padding)
    p_task,     # f32[I, n_pad]  task durations (0 on padding)
    chan_div,   # f32[I]         1 + |K| network channels
    pair_ok=None,  # f32[I, M_pad, M_pad] 1 = rack pair shares a reachable
                #                  subchannel (omitted: no topology in fleet)
    uplift=None,   # f32[I, m_pad]  forced-wired uplift q - min(q, q̌)
    *,
    M_pad: int,
    n_iters: int,
):
    """Batched combined §IV-A bound: one device program for the whole fleet.

    Builds the per-candidate max-plus adjacency (edge cost = p_u + r or
    p_u + min(q, q̌) depending on co-location), accumulates the contention
    terms, and hands both to the fused Pallas kernel
    :func:`repro.kernels.ops.batched_combined_lb`.

    With ``pair_ok``/``uplift`` present, cross edges whose rack pair shares
    no reachable subchannel are charged the wired uplift through the
    kernel's matching-feasibility mask, and the contention side gains the
    serial forced-wired load term (all such edges traverse the single wired
    channel). Both terms stay admissible: any feasible schedule must pay
    ``q`` on forced edges.
    """
    global LB_TRACE_COUNT
    LB_TRACE_COUNT += 1
    B, n_pad = racks.shape
    m_pad = src.shape[1]

    def take(t):
        return jnp.take(t, inst_id, axis=0)

    src_b, dst_b = take(src), take(dst)
    ru = jnp.take_along_axis(racks, src_b, axis=1)
    rv = jnp.take_along_axis(racks, dst_b, axis=1)
    same = ru == rv
    cost = jnp.where(same, take(c_local), take(c_net)) + take(p_src)
    # Batched static-index scatter: padded edges all write -inf at (0, 0),
    # which no real edge can occupy (self-loops are rejected by DagJob).
    w = jnp.full((B, n_pad, n_pad), -jnp.inf, jnp.float32)
    w = w.at[jnp.arange(B)[:, None], src_b, dst_b].set(cost)
    p_b = take(p_task)

    if pair_ok is not None:
        # Per-edge pair connectivity under each candidate's rack choice.
        pk = take(pair_ok)  # [B, M_pad, M_pad]
        ok = (
            jnp.take_along_axis(
                jnp.take_along_axis(pk, ru[:, :, None], axis=1),
                rv[:, :, None],
                axis=2,
            )[..., 0]
            > 0.5
        )
        # Additive matching-feasibility mask for the kernel: 0 on feasible
        # edges, the wired uplift on forced ones (same scatter as ``w``, so
        # parallel edges pair cost and uplift consistently).
        up = jnp.where(same | ok, 0.0, take(uplift))
        mask = jnp.zeros((B, n_pad, n_pad), jnp.float32)
        mask = mask.at[jnp.arange(B)[:, None], src_b, dst_b].set(up)
    else:
        ok = None
        mask = None

    # §IV-A contention terms, accumulated in a fixed sequential order so an
    # instance's bounds are bit-identical under any fleet padding (padded
    # tasks/edges contribute exact zeros).
    def load_body(v, load):
        rv = jax.lax.dynamic_index_in_dim(racks, v, axis=1, keepdims=False)
        pv = jax.lax.dynamic_index_in_dim(p_b, v, axis=1, keepdims=False)
        return load + jnp.where(
            jax.nn.one_hot(rv, M_pad, dtype=bool), pv[:, None], 0.0
        )

    load = jax.lax.fori_loop(0, n_pad, load_body, jnp.zeros((B, M_pad), jnp.float32))
    lb_load = jnp.max(load, axis=1)

    nw = take(net_work)

    if ok is None:

        def work_body(e, acc):
            ne = jax.lax.dynamic_index_in_dim(nw, e, axis=1, keepdims=False)
            se = jax.lax.dynamic_index_in_dim(same, e, axis=1, keepdims=False)
            return acc + jnp.where(se, 0.0, ne)

        work = jax.lax.fori_loop(0, m_pad, work_body, jnp.zeros((B,), jnp.float32))
        extra = jnp.maximum(lb_load, work / take(chan_div))
    else:
        # Forced cross edges pay the full wired duration in the aggregate-work
        # term and, being confined to the single wired channel, also a serial
        # forced-wired load bound.
        nw_eff = nw + jnp.where(ok, 0.0, take(uplift))

        def work_body_topo(e, acc):
            work, forced = acc
            ne = jax.lax.dynamic_index_in_dim(nw_eff, e, axis=1, keepdims=False)
            se = jax.lax.dynamic_index_in_dim(same, e, axis=1, keepdims=False)
            oke = jax.lax.dynamic_index_in_dim(ok, e, axis=1, keepdims=False)
            return (
                work + jnp.where(se, 0.0, ne),
                forced + jnp.where(se | oke, 0.0, ne),
            )

        zero = jnp.zeros((B,), jnp.float32)
        work, forced = jax.lax.fori_loop(0, m_pad, work_body_topo, (zero, zero))
        extra = jnp.maximum(jnp.maximum(lb_load, work / take(chan_div)), forced)

    from repro.kernels import ops as kops

    return kops.batched_combined_lb(w, p_b, extra, mask=mask, n_iters=n_iters)


def batched_lower_bound(inst: ProblemInstance, racks: np.ndarray) -> np.ndarray:
    """Combined §IV-A LB per assignment (critical path + contention terms),
    through the engine's own stage-1 program (`_fleet_lb_device` ->
    `repro.kernels.ops.batched_combined_lb`) on dense size-bucketed
    adjacency blocks. float32[B].

    :func:`repro.core.bounds.combined_lower_bounds` computes the same bound
    from the edge list; it is the portable reference this path is checked
    against.
    """
    racks = np.asarray(racks, dtype=np.int32)
    B = racks.shape[0]
    # LB-only dims: no op tables needed (only the n/m/M buckets and the
    # relaxation depth feed the bound program).
    dims = _fleet_dims([inst])
    B_pad = _bucket(B)
    racks_pad = np.zeros((B_pad, dims.n_pad), dtype=np.int32)
    racks_pad[:B, : inst.job.n_tasks] = racks
    out = _fleet_lb_device(
        jnp.asarray(racks_pad),
        jnp.zeros(B_pad, jnp.int32),
        *_build_lb_arrays([inst], dims),
        M_pad=dims.M_pad,
        n_iters=dims.n_iters,
    )
    return np.asarray(out)[:B]


# ---------------------------------------------------------------------------
# Search driver: lockstep fleet state machines + mega-batch launches
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class VectorizedResult:
    """Outcome of one instance's vectorized search.

    Attributes:
      schedule: the winning assignment re-executed *exactly* by the host
        simulator (OP-checked; can only improve on the device score).
      makespan: ``schedule.makespan``.
      n_evaluated: candidates scored by the stage-2 greedy evaluator.
      best_assignment: int64[n_tasks] winning task->rack assignment.
      n_candidates: candidates considered (``n_evaluated + n_pruned``).
      n_pruned: candidates discarded by the stage-1 §IV-A bound.
      refine_rounds: refinement rounds actually run (sampled regime only).
      strategy_stats: per-strategy refinement counters keyed by strategy
        name (:class:`repro.core.portfolio.StrategyStats`); all-zero when
        the instance was enumerated exhaustively or ``refine_rounds=0``.
    """

    schedule: Schedule
    makespan: float
    n_evaluated: int
    best_assignment: np.ndarray
    n_candidates: int = 0
    n_pruned: int = 0
    refine_rounds: int = 0
    strategy_stats: dict[str, portfolio_mod.StrategyStats] = dataclasses.field(
        default_factory=dict
    )


@dataclasses.dataclass
class FleetResult:
    """Outcome of one fleet mega-batch search.

    ``results[i]`` is bit-for-bit what ``vectorized_search(instances[i])``
    with the same parameters would return. Launch counters tell how many
    device dispatches the whole fleet cost; trace counters how many fresh
    program traces (0 when a same-bucket fleet already warmed the caches,
    at most one per stage otherwise).

    Attributes:
      results: per-instance :class:`VectorizedResult`, in input order.
      makespans: float64[n_instances] of per-instance makespans.
      n_candidates / n_pruned / n_evaluated: fleet-total candidate counters
        (sums of the per-instance counters).
      n_stage1_launches / n_stage2_launches: device dispatches per stage.
      n_stage1_traces / n_stage2_traces: fresh program traces per stage.
      strategy_stats: fleet-aggregated per-strategy refinement counters
        (counter sums; ``weight`` is the mean final allocator weight).
    """

    results: list[VectorizedResult]
    makespans: np.ndarray
    n_candidates: int
    n_pruned: int
    n_evaluated: int
    n_stage1_launches: int
    n_stage2_launches: int
    n_stage1_traces: int
    n_stage2_traces: int
    strategy_stats: dict[str, portfolio_mod.StrategyStats] = dataclasses.field(
        default_factory=dict
    )


# The refinement mutation kernel now lives in repro.core.portfolio (it is
# the "mutation" portfolio strategy); kept aliased for callers of the old
# private name.
_mutate_pool = portfolio_mod.mutate_pool


class _InstanceState:
    """Per-instance search state machine.

    Mirrors the single-instance candidate flow exactly — chunking, buffered
    stage-1 pruning against the running incumbent, fixed-size stage-2
    flushes, strict-improvement incumbent updates — while the fleet driver
    advances all states in lockstep and batches their device work into
    shared launches. Because each state's decisions depend only on its own
    rows (and per-row device results are padding-invariant), fleet results
    equal single-instance results bit for bit.
    """

    def __init__(
        self,
        idx: int,
        inst: ProblemInstance,
        *,
        seed: int,
        max_enumerate: int,
        n_samples: int,
        batch_size: int,
        strategies=None,
        refine_pool: int = 1024,
        patience: int = 1,
        seed_pool: np.ndarray | None = None,
    ):
        self.idx = idx
        self.inst = inst
        self.n = inst.job.n_tasks
        self.batch_size = batch_size
        M = inst.n_racks
        # Bell-number guard: enumerate if the canonical count fits the budget.
        cands = enumerate_assignments(self.n, M, limit=max_enumerate + 1)
        self.sampled = cands.shape[0] > max_enumerate
        if self.sampled:
            rng = np.random.default_rng(seed)
            # Warm-start seed pool: known-good assignments (e.g. incumbents
            # of a previous solve of the same job) lead the sweep so the
            # incumbent — and with it stage-1 pruning — is strong from the
            # first block. Budget-neutral: each seed row displaces one
            # random sample, so warm and cold runs consider the same
            # number of candidates (the random rows are drawn identically
            # and truncated, keeping the RNG stream comparable).
            random_rows = sample_assignments(rng, self.n, M, n_samples)
            parts = [
                enumerate_assignments(self.n, min(2, M), limit=n_samples),
                random_rows,
            ]
            if seed_pool is not None and len(seed_pool):
                seeds = np.asarray(seed_pool, dtype=np.int32).reshape(-1, self.n)
                seeds = (seeds % M)[:n_samples].astype(np.int32)
                parts = [seeds] + parts[:1] + [random_rows[: n_samples - seeds.shape[0]]]
            cands = np.concatenate(parts, axis=0)
        self.cands = cands
        self.pos = 0
        self.buffer: list[np.ndarray] = []
        self.tag_buffer: list[np.ndarray] = []
        self.buffered = 0
        self.best_val = np.inf
        self.best_rack: np.ndarray | None = None
        self.n_eval = 0
        self.n_pruned = 0
        self.n_cands = 0
        self.rng_refine = np.random.default_rng(seed + 1)
        self.refine_rounds_run = 0
        self.prev_best = np.inf
        self.patience = patience
        self.stall = 0
        self.portfolio = portfolio_mod.Portfolio(
            portfolio_mod.build_strategies(strategies),
            inst,
            self.rng_refine,
            pool_size=refine_pool,
        )

    def next_chunk(self) -> np.ndarray | None:
        if self.pos >= self.cands.shape[0]:
            return None
        chunk = self.cands[self.pos : self.pos + self.batch_size]
        self.pos += self.batch_size
        return chunk

    def consider(self, chunk: np.ndarray, lbs: np.ndarray | None, tags=None):
        """Prune a chunk against the incumbent, buffer survivors, emit any
        full stage-2 blocks. ``tags`` are per-row portfolio strategy ids
        (-1 = untagged sweep candidates) threaded through buffering so
        scores can be credited back. Returns [(state, block, true_b, tags)].
        """
        self.n_cands += chunk.shape[0]
        if tags is None:
            tags = np.full(chunk.shape[0], -1, dtype=np.int32)
        if lbs is not None:
            keep = lbs < self.best_val - 1e-6
            self.n_pruned += int((~keep).sum())
            self.portfolio.note_pruned(tags[~keep])
            chunk = chunk[keep]
            tags = tags[keep]
        if chunk.shape[0]:
            self.buffer.append(chunk)
            self.tag_buffer.append(tags)
            self.buffered += chunk.shape[0]
        return self._emit_full()

    def _cat_buffer(self):
        pool = (
            np.concatenate(self.buffer, axis=0)
            if len(self.buffer) > 1
            else self.buffer[0]
        )
        tags = (
            np.concatenate(self.tag_buffer, axis=0)
            if len(self.tag_buffer) > 1
            else self.tag_buffer[0]
        )
        return pool, tags

    def _emit_full(self):
        if self.buffered < self.batch_size:
            return []
        pool, tags = self._cat_buffer()
        bs = self.batch_size
        n_full = (pool.shape[0] // bs) * bs
        blocks = [
            (self, pool[i : i + bs], bs, tags[i : i + bs])
            for i in range(0, n_full, bs)
        ]
        tail, tail_tags = pool[n_full:], tags[n_full:]
        self.buffer = [tail] if tail.shape[0] else []
        self.tag_buffer = [tail_tags] if tail.shape[0] else []
        self.buffered = tail.shape[0]
        return blocks

    def flush_partial(self):
        """Emit everything still buffered (tail padded to the block size;
        pad-row scores are discarded on apply)."""
        blocks = self._emit_full()
        if self.buffered:
            tail, tail_tags = self._cat_buffer()
            true_b = tail.shape[0]
            block = np.concatenate(
                [tail, np.tile(tail[:1], (self.batch_size - true_b, 1))], axis=0
            )
            blocks.append((self, block, true_b, tail_tags))
            self.buffer = []
            self.tag_buffer = []
            self.buffered = 0
        return blocks

    def apply_scores(self, block: np.ndarray, vals: np.ndarray, tags) -> None:
        """Strict-improvement incumbent update over one block's true rows,
        then feed the scored rows back to the portfolio (elite pool plus
        per-strategy credit for tagged refinement rows)."""
        self.n_eval += vals.shape[0]
        prev_best = self.best_val
        j = int(np.argmin(vals))
        if vals[j] < self.best_val:
            self.best_val = float(vals[j])
            self.best_rack = block[j].astype(np.int64)
        self.portfolio.observe(tags, block[: vals.shape[0]], vals, prev_best)


def _run_fleet(
    instances: list[ProblemInstance],
    *,
    max_enumerate: int,
    n_samples: int,
    seeds: list[int],
    batch_size: int,
    refine_rounds: int,
    refine_pool: int,
    strategies=None,
    refine_patience: int | None = None,
    seed_pools=None,
    op_tables=None,
    tracer=None,
):
    """Lockstep fleet driver: one mega-batch launch geometry per stage.

    Every stage-1 launch is ``[I * batch_size]`` rows and every stage-2
    launch ``[I * batch_size]`` rounded up to the device count, so the whole
    fleet run traces (at most) one program per stage no matter how pruning
    fragments the candidate streams.

    ``tracer`` (a :class:`repro.obs.trace.Tracer` or ``None``) records
    child spans that partition the fleet's host time — ``fleet_tables``,
    ``fleet_enumerate``, ``fleet_rounds`` (one per phase of a lockstep
    round), ``fleet_pack`` (one per launch), ``fleet_finish`` — beside a
    ``stage1_launch``/``stage2_launch`` span per device launch, which
    holds a ``_dispatch`` (copies in, program enqueued) and a ``_sync``
    (the blocking copy out) child; the fleet's candidate/prune/launch/
    retrace totals as a ``fleet_solve`` event; the per-strategy
    refinement yields as a ``portfolio_yields`` event; and how often the
    memo of :func:`enumerate_assignments` served or missed a call as the
    ``enumerate_cache_hits``/``enumerate_cache_misses`` counters.
    """
    tr = as_tracer(tracer)
    I = len(instances)
    with tr.span("fleet_tables"):
        if op_tables is None:
            op_tables = [build_op_tables(inst) for inst in instances]
        dims = _fleet_dims(instances, op_tables)
        eval_tables = _build_eval_stack(instances, dims, op_tables)
        lb_args = _build_lb_arrays(instances, dims)
        n_dev = jax.local_device_count()
        fn = _compiled_evaluator(n_dev, dims.m_pad, dims.M_pad, dims.n_chan)
    t2_0, t1_0 = TRACE_COUNT, LB_TRACE_COUNT
    launches = [0, 0]  # [stage1, stage2]

    B1 = I * batch_size
    B2 = I * batch_size
    if B2 % n_dev:
        B2 += n_dev - B2 % n_dev

    # Patience default: stop at the first non-improving round (the
    # pre-portfolio rule) for a single strategy; give multi-strategy
    # portfolios a few stalled rounds so annealing can tunnel.
    if refine_patience is None:
        refine_patience = 1 if portfolio_mod.spec_length(strategies) == 1 else 3
    if seed_pools is None:
        seed_pools = [None] * I
    with tr.span("fleet_enumerate"):
        memo0 = enumerate_assignments.cache_info() if tr.enabled else None
        states = [
            _InstanceState(
                i,
                inst,
                seed=seeds[i],
                max_enumerate=max_enumerate,
                n_samples=n_samples,
                batch_size=batch_size,
                strategies=strategies,
                refine_pool=refine_pool,
                patience=refine_patience,
                seed_pool=seed_pools[i],
            )
            for i, inst in enumerate(instances)
        ]
        if tr.enabled:
            memo = enumerate_assignments.cache_info()
            tr.count("enumerate_cache_hits", memo.hits - memo0.hits)
            tr.count("enumerate_cache_misses", memo.misses - memo0.misses)

    def pack(B, rows_of) -> tuple[np.ndarray, np.ndarray]:
        # rows_of: [(state, rows[<= batch_size, state.n])], one slot each.
        with tr.span("fleet_pack"):
            rack = np.zeros((B, dims.n_pad), dtype=np.int32)
            iid = np.zeros(B, dtype=np.int32)
            for s, (st, rows) in enumerate(rows_of):
                lo = s * batch_size
                rack[lo : lo + rows.shape[0], : st.n] = rows
                iid[lo : lo + batch_size] = st.idx
        return rack, iid

    def launch_stage2(blocks) -> None:
        # blocks: [(state, block[batch_size, state.n], true_b, tags)],
        # applied in order so per-state incumbent evolution matches the
        # solo flow.
        for g0 in range(0, len(blocks), I):
            group = blocks[g0 : g0 + I]
            rack, iid = pack(B2, [(st, blk) for st, blk, _tb, _tg in group])
            with tr.span("stage2_launch", instances=I, rows=B2, n_pad=dims.n_pad):
                with tr.span("stage2_dispatch"):
                    vals = fn(jnp.asarray(rack), jnp.asarray(iid), *eval_tables)
                with tr.span("stage2_sync"):
                    vals = np.asarray(vals)
            launches[1] += 1
            with tr.span("fleet_rounds"):
                for s, (st, blk, tb, tg) in enumerate(group):
                    lo = s * batch_size
                    st.apply_scores(blk, vals[lo : lo + tb], tg)

    def launch_stage1(reqs):
        # reqs: [(state, chunk)] -> per-request float32 LB arrays.
        if not reqs:
            return []
        out = [np.empty(chunk.shape[0], np.float32) for _, chunk in reqs]
        pieces = []
        for ri, (_st, chunk) in enumerate(reqs):
            for off in range(0, chunk.shape[0], batch_size):
                pieces.append((ri, off, chunk[off : off + batch_size]))
        for g0 in range(0, len(pieces), I):
            group = pieces[g0 : g0 + I]
            rack, iid = pack(B1, [(reqs[ri][0], rows) for ri, _off, rows in group])
            with tr.span(
                "stage1_launch",
                instances=I,
                rows=B1,
                n_pad=dims.n_pad,
                n_iters=dims.n_iters,
            ):
                with tr.span("stage1_dispatch"):
                    lbs = _fleet_lb_device(
                        jnp.asarray(rack),
                        jnp.asarray(iid),
                        *lb_args,
                        M_pad=dims.M_pad,
                        n_iters=dims.n_iters,
                    )
                with tr.span("stage1_sync"):
                    lbs = np.asarray(lbs)
            launches[0] += 1
            with tr.span("fleet_rounds"):
                for s, (ri, off, rows) in enumerate(group):
                    lo = s * batch_size
                    out[ri][off : off + rows.shape[0]] = lbs[lo : lo + rows.shape[0]]
        return out

    def lockstep_round(propose, flush: bool) -> None:
        # One lockstep round. ``propose()`` gives [(state, chunk, tags)];
        # chunks whose state has a finite incumbent share the stage-1
        # launches, the survivors are buffered (each state's tail flushed
        # too when ``flush``) and their blocks share the stage-2 launches.
        with tr.span("fleet_rounds"):
            entries = propose()
            reqs = [
                (st, chunk)
                for st, chunk, _tags in entries
                if np.isfinite(st.best_val) and chunk.shape[0]
            ]
        lbs_list = launch_stage1(reqs)
        with tr.span("fleet_rounds"):
            lbs_by_state = {id(st): lbs for (st, _), lbs in zip(reqs, lbs_list)}
            blocks = []
            for st, chunk, tags in entries:
                blocks += st.consider(chunk, lbs_by_state.get(id(st)), tags=tags)
                if flush:
                    blocks += st.flush_partial()
        launch_stage2(blocks)

    def sweep_chunks():
        chunks = ((st, st.next_chunk()) for st in states)
        return [(st, chunk, None) for st, chunk in chunks if chunk is not None]

    # Main sweep: one chunk per instance per lockstep round.
    while any(st.pos < st.cands.shape[0] for st in states):
        lockstep_round(sweep_chunks, flush=False)
    with tr.span("fleet_rounds"):
        blocks = []
        for st in states:
            blocks += st.flush_partial()
    launch_stage2(blocks)
    for st in states:
        assert st.best_rack is not None

    # Refinement: the lockstep strategy portfolio for sampled-regime
    # instances. Each round every active instance's portfolio proposes one
    # tagged candidate pool (budget split across strategies by recent
    # yield); proposals ride the shared stage-1/stage-2 launches exactly
    # like sweep candidates. An instance stops independently after
    # ``patience`` consecutive non-improving rounds.
    active = [st for st in states if st.sampled] if refine_rounds > 0 else []

    def refine_pools():
        entries = []
        for st in active:
            st.prev_best = st.best_val
            pool, tags = st.portfolio.begin_round(st.best_rack, st.best_val)
            entries.append((st, pool, tags))
        return entries

    for _ in range(refine_rounds):
        if not active:
            break
        lockstep_round(refine_pools, flush=True)
        with tr.span("fleet_rounds"):
            nxt = []
            for st in active:
                st.portfolio.end_round(st.best_rack, st.best_val)
                st.refine_rounds_run += 1
                if st.best_val < st.prev_best - 1e-9:
                    st.stall = 0
                else:
                    st.stall += 1
                if st.stall < st.patience:
                    nxt.append(st)
        active = nxt

    with tr.span("fleet_finish"):
        results = []
        for st in states:
            sched = simulate(st.inst, st.best_rack)
            results.append(
                VectorizedResult(
                    schedule=sched,
                    makespan=sched.makespan,
                    n_evaluated=st.n_eval,
                    best_assignment=st.best_rack,
                    n_candidates=st.n_cands,
                    n_pruned=st.n_pruned,
                    refine_rounds=st.refine_rounds_run,
                    strategy_stats=st.portfolio.stats,
                )
            )
        stats = {
            "n_stage1_launches": launches[0],
            "n_stage2_launches": launches[1],
            "n_stage1_traces": LB_TRACE_COUNT - t1_0,
            "n_stage2_traces": TRACE_COUNT - t2_0,
        }
        if tr.enabled:
            tr.count("stage1_launches", launches[0])
            tr.count("stage2_launches", launches[1])
            tr.event(
                "fleet_solve",
                n_instances=I,
                n_candidates=sum(s.n_cands for s in states),
                n_pruned=sum(s.n_pruned for s in states),
                n_evaluated=sum(s.n_eval for s in states),
                **stats,
            )
            merged = portfolio_mod.merge_strategy_stats(
                s.portfolio.stats for s in states
            )
            if merged:
                tr.event(
                    "portfolio_yields",
                    strategies=portfolio_mod.stats_snapshot(merged),
                )
    return results, stats


def vectorized_search(
    inst: ProblemInstance,
    max_enumerate: int = 200_000,
    n_samples: int = 8192,
    seed: int = 0,
    batch_size: int = 8192,
    refine_rounds: int = 4,
    refine_pool: int = 1024,
    strategies=None,
    refine_patience: int | None = None,
    seed_pool: np.ndarray | None = None,
    tracer=None,
) -> VectorizedResult:
    """Best-of-batch schedule search with bound-driven pruning.

    Enumerates all canonical assignments when that is small enough, else
    samples. Each batch first passes through the combined §IV-A Pallas
    bound (stage 1); only candidates whose bound beats the incumbent are
    scheduled by the batched greedy evaluator (stage 2). In the sampled
    regime the incumbent is refined by the strategy portfolio of
    :mod:`repro.core.portfolio`. The winner is re-executed with the exact
    host simulator (which can only improve on the vectorized non-delay
    score) and verified. The fleet-of-one special case of
    :func:`schedule_fleet`.

    Args:
      inst: the problem instance (``n_wireless=0`` models wired-only
        operation).
      max_enumerate: enumerate exhaustively iff the canonical assignment
        count (restricted growth strings) is at most this; else sample.
      n_samples: random candidates in the sampled regime (plus a 2-rack
        canonical prefix of the same size).
      seed: master seed. Sampling uses ``default_rng(seed)``; refinement
        draws from ``default_rng(seed + 1)``. Fixed seed + fixed
        parameters => bit-identical results across runs and across fleet
        packings (device scores are float32-deterministic on one backend).
      batch_size: stage-2 block size; candidate streams are chunked,
        pruned, and re-blocked to exactly this many rows per launch.
      refine_rounds: max refinement rounds (sampled regime only).
      refine_pool: per-round refinement candidate budget, split across the
        portfolio's strategies by recent yield.
      strategies: refinement portfolio spec for
        :func:`repro.core.portfolio.build_strategies`. ``None`` (default)
        is mutation-only local search — bit-for-bit the pre-portfolio
        refinement loop; ``"portfolio"`` enables
        mutation + elite crossover + simulated annealing under the
        multiplicative-weights budget allocator.
      refine_patience: stop refining after this many consecutive
        non-improving rounds. ``None`` => 1 for a single strategy (the
        pre-portfolio rule), 3 for a multi-strategy portfolio.
      seed_pool: optional int[S, n_tasks] warm-start assignments (e.g.
        incumbents from a previous solve of the same job) injected at the
        head of the sampled-regime sweep. Budget-neutral: each seed
        displaces one random sample, so ``n_candidates`` is unchanged.
        Labels are folded into ``[0, n_racks)`` with a modulo, letting
        incumbents from a differently-sized resource view seed a residual
        re-solve. Ignored in the exhaustive-enumeration regime (the sweep
        already covers every canonical assignment). Scored seeds enter
        the refinement portfolio's elite pool like any sweep candidate,
        so crossover can recombine them from round one.
      tracer: optional :class:`repro.obs.trace.Tracer` recording the
        fleet driver's phase and launch spans and the solve's candidate /
        prune / retrace totals, as :func:`schedule_fleet` does (``None`` =
        no tracing; bit-identical).

    Returns:
      :class:`VectorizedResult` (per-strategy refinement counters in
      ``strategy_stats``).
    """
    tr = as_tracer(tracer)
    with tr.span("schedule_fleet", n_instances=1):
        results, _ = _run_fleet(
            [inst],
            max_enumerate=max_enumerate,
            n_samples=n_samples,
            seeds=[seed],
            batch_size=batch_size,
            refine_rounds=refine_rounds,
            refine_pool=refine_pool,
            strategies=strategies,
            refine_patience=refine_patience,
            seed_pools=[seed_pool],
            tracer=tr,
        )
    return results[0]


def schedule_fleet(
    instances,
    max_enumerate: int = 200_000,
    n_samples: int = 8192,
    seed=0,
    batch_size: int = 8192,
    refine_rounds: int = 4,
    refine_pool: int = 1024,
    strategies=None,
    refine_patience: int | None = None,
    seed_pools=None,
    op_tables=None,
    tracer=None,
) -> FleetResult:
    """Solve a heterogeneous fleet of instances in one padded mega-batch.

    All instances are padded to one shared size bucket and their candidate
    streams advance in lockstep: each round contributes one chunk per
    instance to a single stage-1 bound launch and the survivors to a single
    sharded stage-2 evaluation launch, so the whole fleet compiles at most
    one program per stage and amortizes every dispatch across jobs.
    Refinement proposals (one tagged pool per instance per round, from that
    instance's private strategy portfolio) ride the same shared launches.

    Args:
      instances: iterable of :class:`ProblemInstance` (at least one).
      seed: scalar (shared by all instances) or one seed per instance.
      strategies: portfolio spec shared by all instances; each instance
        gets its own freshly built strategy objects, so pass registry
        names (e.g. ``"portfolio"`` or ``("mutation", "crossover")``) or
        zero-arg factories — live Strategy objects would alias state
        across the fleet and are rejected for fleets of more than one.
      seed_pools: ``None``, or one warm-start pool per instance (each
        ``None`` or int[S, n_tasks]; see ``seed_pool`` on
        :func:`vectorized_search`). The online serving layer uses this to
        re-optimize still-queued jobs from their incumbent assignments.
      op_tables: ``None``, or one prebuilt
        :class:`~repro.core.simulator.OpTables` per instance. Tables
        depend only on ``inst.job``, so a caller that re-solves the same
        jobs across epochs (the online service) can build each job's
        tables once and skip the per-launch rebuild; passing ``None``
        builds them here. Results are bit-identical either way.
      tracer: optional :class:`repro.obs.trace.Tracer`. Records a
        ``schedule_fleet`` span whose children partition it: the host
        phases (``fleet_tables``, ``fleet_enumerate``, ``fleet_rounds``,
        ``fleet_pack``, ``fleet_finish``) and one ``stage1_launch`` /
        ``stage2_launch`` per device launch, each split into
        ``_dispatch`` and ``_sync``; plus ``fleet_solve`` (candidates /
        pruned / launches / retraces) and ``portfolio_yields`` decision
        events (see :func:`_run_fleet`). ``None`` (default) traces
        nothing and is bit-identical.
      (remaining arguments: see :func:`vectorized_search`.)

    Determinism / solo equivalence: with the same seed and parameters,
    ``results[i]`` is bit-for-bit identical to
    ``vectorized_search(instances[i], ...)`` run alone — fleet packing
    never changes any per-instance score, prune decision, or RNG draw.

    Returns:
      :class:`FleetResult` with per-instance results, fleet candidate /
      launch / trace counters, and fleet-aggregated ``strategy_stats``.
    """
    instances = list(instances)
    if not instances:
        raise ValueError("schedule_fleet needs at least one instance")
    if len(instances) > 1 and strategies is not None and not isinstance(strategies, str):
        for item in strategies:
            if (
                not isinstance(item, (str, type))
                and hasattr(item, "propose")
            ):
                raise ValueError(
                    "fleets need per-instance strategy state: pass names or "
                    "factories, not live Strategy objects"
                )
    if np.ndim(seed) == 0:
        seeds = [int(seed)] * len(instances)
    else:
        seeds = [int(s) for s in seed]
        if len(seeds) != len(instances):
            raise ValueError("one seed per instance required")
    if seed_pools is not None and len(seed_pools) != len(instances):
        raise ValueError("one seed pool (or None) per instance required")
    if op_tables is not None and len(op_tables) != len(instances):
        raise ValueError("one OpTables per instance required")
    tr = as_tracer(tracer)
    with tr.span("schedule_fleet", n_instances=len(instances)):
        results, stats = _run_fleet(
            instances,
            max_enumerate=max_enumerate,
            n_samples=n_samples,
            seeds=seeds,
            batch_size=batch_size,
            refine_rounds=refine_rounds,
            refine_pool=refine_pool,
            strategies=strategies,
            refine_patience=refine_patience,
            seed_pools=seed_pools,
            op_tables=op_tables,
            tracer=tr,
        )
    return FleetResult(
        results=results,
        makespans=np.asarray([r.makespan for r in results]),
        n_candidates=sum(r.n_candidates for r in results),
        n_pruned=sum(r.n_pruned for r in results),
        n_evaluated=sum(r.n_evaluated for r in results),
        strategy_stats=portfolio_mod.merge_strategy_stats(
            r.strategy_stats for r in results
        ),
        **stats,
    )
