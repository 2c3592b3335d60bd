"""Kernel micro-benchmarks.

NOTE (CPU container): Pallas kernels execute in interpret mode here, so
wall-clock numbers characterize the HOST fallback, not TPU performance —
TPU performance is assessed structurally via §Roofline. The jnp flash twin
is XLA-compiled and its timing is meaningful on this host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timer
from repro.kernels import ops
from repro.models.flash import flash_attention as jnp_flash


def run():
    rng = np.random.default_rng(0)
    B, S, H, KV, D = 1, 1024, 8, 2, 128
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.bfloat16)

    f = jax.jit(lambda q, k, v: jnp_flash(q, k, v, True, 256))
    f(q, k, v).block_until_ready()
    _, t = timer(lambda: f(q, k, v).block_until_ready())
    flops = 2 * 2 * B * S * S * H * D / 2  # causal half
    emit("jnp_flash_fwd_1k", 1e6 * t, f"gflops_s={flops / t / 1e9:.1f}")

    _, t = timer(
        lambda: ops.flash_attention(q, k, v, True, 128, 128).block_until_ready(),
        repeats=1,
    )
    emit("pallas_flash_interpret_1k", 1e6 * t, "interpret-mode(host)")

    qd = jnp.asarray(rng.standard_normal((4, H, D)), jnp.float32)
    kd = jnp.asarray(rng.standard_normal((4, 4096, KV, D)), jnp.float32)
    _, t = timer(
        lambda: ops.decode_attention(qd, kd, kd, jnp.int32(4096)).block_until_ready(),
        repeats=1,
    )
    emit("pallas_decode_interpret_4k", 1e6 * t, "interpret-mode(host)")

    w = jnp.asarray(
        np.where(np.triu(np.ones((4096, 16, 16)), 1), 5.0, -np.inf), jnp.float32
    )
    _, t = timer(lambda: ops.batched_critical_path(w).block_until_ready(), repeats=1)
    emit("pallas_cpm_interpret_4096x16", 1e6 * t, "interpret-mode(host)")
    _, t = timer(
        lambda: ops.batched_critical_path(w, block_b=256).block_until_ready(),
        repeats=1,
    )
    emit("pallas_cpm_interpret_4096x16_bb256", 1e6 * t, "interpret-mode(host)")


def run_search_engine():
    """The two stages of the vectorized search substrate on one size bucket."""
    from repro.core import ProblemInstance, random_job
    from repro.core.bounds import combined_lower_bounds
    from repro.core.vectorized import (
        batched_lower_bound,
        make_batched_evaluator,
        sample_assignments,
    )

    rng = np.random.default_rng(0)
    job = random_job(rng, None, n_tasks=10, rho=0.5)
    inst = ProblemInstance(job=job, n_racks=6, n_wireless=1)
    racks = sample_assignments(rng, 10, 6, 8192)

    evaluate = make_batched_evaluator(inst)
    np.asarray(evaluate(racks))  # compile the bucket
    _, t = timer(lambda: np.asarray(evaluate(racks)))
    emit("optable_scan_eval_8192xN10", 1e6 * t, f"cands_per_s={racks.shape[0] / t:.0f}")

    batched_lower_bound(inst, racks)  # compile the bucket
    _, t = timer(lambda: batched_lower_bound(inst, racks))
    emit("pallas_cpm_lb_8192xN10", 1e6 * t, f"cands_per_s={racks.shape[0] / t:.0f}")

    _, t = timer(lambda: combined_lower_bounds(inst, racks))
    emit("edgelist_lb_8192xN10", 1e6 * t, f"cands_per_s={racks.shape[0] / t:.0f}")


def main():
    run()
    run_search_engine()


if __name__ == "__main__":
    main()
