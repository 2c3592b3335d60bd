"""Public jit'd wrappers for the Pallas kernels.

On CPU hosts (any unit-test environment) the kernels run in
``interpret=True`` mode — the kernel body executes as traced JAX ops, so
correctness is identical while TPU Mosaic lowering is exercised only on real
hardware. The wrapper picks the mode from the default backend.
"""

from __future__ import annotations

import jax

from repro.kernels.cpm import batched_combined_lb as _combined_lb
from repro.kernels.cpm import batched_critical_path as _cpm
from repro.kernels.decode_attention import decode_attention_fwd as _decode
from repro.kernels.flash_attention import flash_attention_fwd as _flash

__all__ = [
    "flash_attention",
    "decode_attention",
    "batched_critical_path",
    "batched_combined_lb",
]


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, causal=True, block_q=128, block_kv=128):
    return _flash(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=_interpret(),
    )


def decode_attention(q, k, v, kv_len, block_kv=512):
    return _decode(q, k, v, kv_len, block_kv=block_kv, interpret=_interpret())


def batched_critical_path(w, block_b=None, n_iters=None):
    return _cpm(w, block_b=block_b, n_iters=n_iters, interpret=_interpret())


def batched_combined_lb(w, p, extra, mask=None, block_b=None, n_iters=None):
    return _combined_lb(
        w, p, extra, mask=mask, block_b=block_b, n_iters=n_iters,
        interpret=_interpret(),
    )
