"""Pallas TPU batched critical-path (longest path) and combined-LB kernels.

The inner bound evaluation of the paper's scheduler, vectorized: given a
batch of max-plus adjacency matrices w[B, n, n] (w[u, v] = edge cost
p_u + transfer(u,v), -inf when no edge), compute dist[B, n] — the longest
path from any source to each node — by n-1 Bellman relaxation rounds:

    dist[v] <- max(dist[v], max_u dist[u] + w[u, v])

Each round is a max-plus matrix-vector product, mapped to VPU broadcast
adds + row-max reductions on a [bb, n, n] VMEM block. Graphs are padded to
the TPU lane width (n <= 128) — the paper's production jobs have <= 10
tasks, so thousands of candidate assignments evaluate in one launch. The
row block ``bb`` is derived from ``n`` (:func:`block_rows`) so that the
lane-padded block fits the chip's scoped VMEM.

Two entry points share the relaxation loop:

  :func:`batched_critical_path` returns the raw dist[B, n] table.

  :func:`batched_combined_lb` fuses the paper's full §IV-A stage-1 bound
  into one launch: lb[b] = max(max_v dist[b, v] + p[b, v], extra[b]), where
  ``extra`` carries the contention terms (per-rack work and aggregate
  wired+wireless channel work) precomputed per batch row. Taking the max of
  the critical-path bound and the contention bounds keeps the result
  admissible — each term individually lower-bounds the makespan — while
  pruning dense instances the contention-free critical path cannot touch.
  ``p`` is per-row (heterogeneous mega-batches carry a different job per
  row), and all-padding rows (w = -inf, p = 0, extra = -inf) yield lb = 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["batched_critical_path", "batched_combined_lb", "block_rows"]

NEG_INF = -1e30

# Bytes of one lane-padded f32 (bb, n, n) input block. Mosaic pads the minor
# (n, n) dims to (8k, 128k) tiles, and every such input is double-buffered
# next to the relaxation temporaries. On TPU v5e the masked kernel (two such
# inputs) stops compiling between 2 and 3 MiB per block at n = 64, and both
# kernels at 8 MiB for every n; 1 MiB keeps at least 2x headroom at every
# n bucket.
_BLOCK_BYTES = 1 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def block_rows(B: int, n: int) -> int:
    """Batch rows per grid step for a [B, n, n] launch: the whole batch if it
    fits the VMEM block budget, else the largest multiple of 8 that does
    (at least 8)."""
    row_bytes = _round_up(n, 8) * _round_up(n, 128) * 4
    return min(B, max(8, _BLOCK_BYTES // row_bytes // 8 * 8))


def _relax(w, bb: int, n: int, n_iters: int):
    """dist[bb, n] after ``n_iters`` Bellman max-plus relaxation rounds —
    the shared loop body of both kernels."""
    dist = jnp.zeros((bb, n), jnp.float32)

    def body(_, dist):
        # cand[b, u, v] = dist[b, u] + w[b, u, v]
        cand = dist[:, :, None] + w
        return jnp.maximum(dist, jnp.max(cand, axis=1))

    return jax.lax.fori_loop(0, n_iters, body, dist)


def _kernel(w_ref, o_ref, *, n: int, bb: int, n_iters: int):
    o_ref[...] = _relax(w_ref[...], bb, n, n_iters)


@functools.partial(jax.jit, static_argnames=("block_b", "n_iters", "interpret"))
def batched_critical_path(
    w: jax.Array,  # [B, n, n] float32 max-plus adjacency (-inf = no edge)
    block_b: int | None = None,
    n_iters: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """dist[B, n]: longest path into each node by Bellman relaxation rounds.

    ``n_iters`` bounds the relaxation count (default n-1, the worst-case DAG
    depth). Callers that pad graphs to a size bucket should pass the true
    depth bound so padding does not add rounds. ``block_b`` (rows per grid
    step) defaults to :func:`block_rows`; it changes no row's arithmetic.
    """
    B, n, _ = w.shape
    if n_iters is None:
        n_iters = n - 1
    n_iters = max(0, min(n_iters, n - 1))
    bb = block_rows(B, n) if block_b is None else min(block_b, B)
    pad = (-B) % bb
    w = jnp.where(jnp.isfinite(w), w, NEG_INF).astype(jnp.float32)
    if pad:
        w = jnp.concatenate([w, jnp.full((pad, n, n), NEG_INF, jnp.float32)], 0)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, bb=bb, n_iters=n_iters),
        grid=((B + pad) // bb,),
        in_specs=[pl.BlockSpec((bb, n, n), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((bb, n), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B + pad, n), jnp.float32),
        interpret=interpret,
    )(w)
    return out[:B]


def _lb_kernel(w_ref, p_ref, x_ref, o_ref, *, n: int, bb: int, n_iters: int):
    dist = _relax(w_ref[...], bb, n, n_iters)
    # Fused epilogue: close the path bound with the sink task's own duration
    # and fold in the precomputed contention terms (max keeps admissibility).
    lb = jnp.max(dist + p_ref[...], axis=1, keepdims=True)  # [bb, 1]
    o_ref[...] = jnp.maximum(lb, x_ref[...])


def _lb_kernel_masked(
    w_ref, p_ref, x_ref, m_ref, o_ref, *, n: int, bb: int, n_iters: int
):
    # Matching-feasibility mask, additive form: m[u, v] = 0 where the
    # optimistic (wireless-augmented) edge cost in w is reachable under the
    # topology, = the wired-minus-wireless cost uplift where it is not.
    # Adding before relaxation keeps -inf (no edge) at -inf and raises
    # infeasible network edges to their forced-wired cost.
    dist = _relax(w_ref[...] + m_ref[...], bb, n, n_iters)
    lb = jnp.max(dist + p_ref[...], axis=1, keepdims=True)  # [bb, 1]
    o_ref[...] = jnp.maximum(lb, x_ref[...])


@functools.partial(jax.jit, static_argnames=("block_b", "n_iters", "interpret"))
def batched_combined_lb(
    w: jax.Array,      # [B, n, n] float32 max-plus adjacency (-inf = no edge)
    p: jax.Array,      # [B, n] float32 per-row task durations (0 on padding)
    extra: jax.Array,  # [B] or [B, 1] float32 contention bound (-inf to disable)
    mask: jax.Array | None = None,  # [B, n, n] float32 feasibility uplift
    block_b: int | None = None,
    n_iters: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """lb[B] = max(critical-path bound, contention bound) per batch row.

    The §IV-A combined stage-1 bound of the batched pruner: the Bellman
    relaxation of :func:`batched_critical_path` plus a fused epilogue that
    adds the sink task duration (max_v dist[v] + p[v]) and maxes in the
    per-row ``extra`` contention terms, so one kernel launch emits the final
    admissible bound. ``n_iters`` and ``block_b`` as in
    :func:`batched_critical_path`.

    ``mask`` is the topology layer's matching-feasibility mask in additive
    form: 0 where the row's placement of edge (u, v) can reach a common
    wireless subchannel (w's optimistic cost stands), and the non-negative
    forced-wired cost uplift (q - min(q, q̌)) where it cannot — the kernel
    relaxes over ``w + mask``, so infeasible picks are priced at the wired
    channel before the bound is taken. ``mask=None`` (all topologies
    unrestricted) compiles the exact pre-topology kernel, bit-identical.
    """
    B, n, _ = w.shape
    if n_iters is None:
        n_iters = n - 1
    n_iters = max(0, min(n_iters, n - 1))
    bb = block_rows(B, n) if block_b is None else min(block_b, B)
    pad = (-B) % bb
    w = jnp.where(jnp.isfinite(w), w, NEG_INF).astype(jnp.float32)
    p = p.astype(jnp.float32)
    extra = jnp.asarray(extra, jnp.float32).reshape(B, 1)
    extra = jnp.where(jnp.isfinite(extra), extra, NEG_INF)
    if mask is not None:
        mask = jnp.asarray(mask, jnp.float32)
    if pad:
        w = jnp.concatenate([w, jnp.full((pad, n, n), NEG_INF, jnp.float32)], 0)
        p = jnp.concatenate([p, jnp.zeros((pad, n), jnp.float32)], 0)
        extra = jnp.concatenate([extra, jnp.full((pad, 1), NEG_INF, jnp.float32)], 0)
        if mask is not None:
            mask = jnp.concatenate(
                [mask, jnp.zeros((pad, n, n), jnp.float32)], 0
            )
    if mask is None:
        out = pl.pallas_call(
            functools.partial(_lb_kernel, n=n, bb=bb, n_iters=n_iters),
            grid=((B + pad) // bb,),
            in_specs=[
                pl.BlockSpec((bb, n, n), lambda b: (b, 0, 0)),
                pl.BlockSpec((bb, n), lambda b: (b, 0)),
                pl.BlockSpec((bb, 1), lambda b: (b, 0)),
            ],
            out_specs=pl.BlockSpec((bb, 1), lambda b: (b, 0)),
            out_shape=jax.ShapeDtypeStruct((B + pad, 1), jnp.float32),
            interpret=interpret,
        )(w, p, extra)
    else:
        out = pl.pallas_call(
            functools.partial(_lb_kernel_masked, n=n, bb=bb, n_iters=n_iters),
            grid=((B + pad) // bb,),
            in_specs=[
                pl.BlockSpec((bb, n, n), lambda b: (b, 0, 0)),
                pl.BlockSpec((bb, n), lambda b: (b, 0)),
                pl.BlockSpec((bb, 1), lambda b: (b, 0)),
                pl.BlockSpec((bb, n, n), lambda b: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((bb, 1), lambda b: (b, 0)),
            out_shape=jax.ShapeDtypeStruct((B + pad, 1), jnp.float32),
            interpret=interpret,
        )(w, p, extra, mask)
    return out[:B, 0]
