"""Pallas TPU decode attention: one query token against a long KV cache.

Flash-decoding structure: grid (B·KV, n_kv_blocks) with the KV dimension
``arbitrary`` so VMEM scratch (acc, m, l) accumulates across cache blocks.
The query block is [G, D] (all group heads of one kv head); a kv-length
mask handles partially-filled caches (decode position < T_max).

Hotspot of decode_32k / long_500k cells: the entire cache streams HBM→VMEM
once, with no [1, T] score materialization in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_fwd"]

NEG_INF = -1e30


def _kernel(
    kvlen_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, bk: int, G: int, n_kv: int, scale: float,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]  # [G, D]
    k = k_ref[0]  # [bk, D]
    v = v_ref[0]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, bk]
    kv_len = kvlen_ref[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1) + j * bk
    s = jnp.where(cols < kv_len, s, NEG_INF)

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


@functools.partial(jax.jit, static_argnames=("block_kv", "interpret"))
def decode_attention_fwd(
    q: jax.Array,      # [B, H, D] single-position queries
    k: jax.Array,      # [B, T, KV, D] cache
    v: jax.Array,
    kv_len: jax.Array,  # [] or [B] valid cache length
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    bk = min(block_kv, T)
    assert T % bk == 0
    nk = T // bk
    scale = 1.0 / np.sqrt(D)

    qf = q.reshape(B, KV, G, D).reshape(B * KV, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, T, D)
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    kvlen_f = jnp.repeat(kv_len, KV)  # [B*KV]

    kernel = functools.partial(_kernel, bk=bk, G=G, n_kv=nk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * KV, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, j: (b,)),
            pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(kvlen_f, qf, kf, vf)
    return out.reshape(B, KV, G, D).reshape(B, H, D)
