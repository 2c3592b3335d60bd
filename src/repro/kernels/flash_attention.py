"""Pallas TPU flash attention (forward), GQA-aware.

Grid (B·KV, n_q_blocks, n_kv_blocks) with ``arbitrary`` semantics on the KV
dimension: VMEM scratch (acc, m, l) persists across KV steps, implementing
online softmax without materializing the [S, T] score matrix in HBM. Query
rows fold the GQA group dimension (bq queries × G group heads per block row)
so the MXU sees [bq·G, D] × [D, bk] matmuls with D = head_dim = 128-aligned.

This is the TPU-optimized twin of models/flash.py (the pure-jnp reference
with custom VJP used by the CPU dry-run); tests sweep shapes/dtypes and
assert allclose between the two in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref,  # blocks
    acc_ref, m_ref, l_ref,       # VMEM scratch
    *, bq: int, bk: int, G: int, causal: bool, n_kv: int, scale: float,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # [bq*G, D]
    k = k_ref[0]  # [bk, D]
    v = v_ref[0]  # [bk, D]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq*G, bk]

    if causal:
        i = pl.program_id(1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq * G, bk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq * G, bk), 1)
        q_idx = i * bq + rows // G
        k_idx = j * bk + cols
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new

    @pl.when(j == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_kv", "interpret")
)
def flash_attention_fwd(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, T, KV, D]
    v: jax.Array,
    causal: bool = True,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_kv, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    nq, nk = S // bq, T // bk
    scale = 1.0 / np.sqrt(D)

    # [B, S, KV, G, D] -> [B*KV, S*G, D] with query-major, group-minor rows.
    qf = (
        q.reshape(B, S, KV, G, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B * KV, S * G, D)
    )
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, T, D)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, G=G, causal=causal, n_kv=nk, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * KV, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq * G, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq * G, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, S * G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * G, D), jnp.float32),
            pltpu.VMEM((bq * G,), jnp.float32),
            pltpu.VMEM((bq * G,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return (
        out.reshape(B, KV, S, G, D).transpose(0, 2, 1, 3, 4).reshape(B, S, H, D)
    )
