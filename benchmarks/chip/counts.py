"""Operations and bytes of the stage-1 bound kernel, from its launch shapes.

``batched_combined_lb`` takes ``w[B, n, n]``, ``p[B, n]``, ``extra[B]`` and,
with a topology, ``mask[B, n, n]`` (float32), and returns ``lb[B]``. It
relaxes ``dist[B, n]`` for ``n_iters`` rounds (``dist[v] = max(dist[v],
max_u dist[u] + w[u, v])``: ``n`` adds and ``n`` maxima per ``(b, v)``),
adds the mask once before that, and closes each row with ``n`` adds and
``n`` maxima (``max_v dist[v] + p[v]``, then the max with ``extra``).

The counts are of the algorithm's inputs, output and arithmetic at the
launch shape, not of the lane padding any implementation adds, so they read
the same whatever computes the bound. Every operation is a float32 add or
max on the vector unit; none is a matrix product.
"""

from __future__ import annotations

__all__ = ["stage1_ops", "stage1_bytes", "min_seconds"]

F32 = 4


def stage1_ops(B: int, n: int, n_iters: int, masked: bool) -> int:
    relax = n_iters * 2 * B * n * n
    mask = B * n * n if masked else 0
    close = 2 * B * n
    return relax + mask + close


def stage1_bytes(B: int, n: int, masked: bool) -> int:
    inputs = B * n * n + B * n + B + (B * n * n if masked else 0)
    return F32 * (inputs + B)


def min_seconds(ops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
