"""Tests for the roofline HLO analyzer and the scheduler->training planner."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo
from repro.distribution.plan import (
    LinkSpec,
    backward_profile,
    plan_gradient_schedule,
    replan,
)


def test_analyzer_multiplies_scan_trip_counts():
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jnp.ones((64, 64))
    compiled = jax.jit(f).lower(x).compile()
    cost = analyze_hlo(compiled.as_text())
    assert cost.flops == pytest.approx(10 * 2 * 64**3)
    # XLA's own analysis is known NOT to multiply (the reason this exists).
    xla = compiled.cost_analysis().get("flops", 0.0)
    assert xla < cost.flops / 2


def test_analyzer_nested_scans():
    def g(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    x = jnp.ones((32, 32))
    cost = analyze_hlo(jax.jit(g).lower(x).compile().as_text())
    assert cost.flops == pytest.approx(15 * 2 * 32**3)


def test_analyzer_counts_hbm_and_no_collectives_on_1_device():
    def f(x, w):
        return jax.nn.relu(x @ w)

    x = jnp.ones((128, 256))
    w = jnp.ones((256, 64))
    cost = analyze_hlo(jax.jit(f).lower(x, w).compile().as_text())
    assert cost.flops == pytest.approx(2 * 128 * 256 * 64)
    assert cost.hbm_bytes > 0
    assert cost.total_collective_bytes == 0.0


def test_backward_profile_shapes():
    from repro.configs import get_config

    cfg = get_config("llama3_2_3b")
    secs, bts = backward_profile(cfg, tokens_per_device=4096, groups=8)
    assert secs.shape == (8,) and bts.shape == (8,)
    assert (secs > 0).all() and (bts > 0).all()
    # total grad bytes ~ 2 bytes/param for the transformer trunk
    assert bts.sum() == pytest.approx(2 * 28 * (3072 * 24 * 128 * 2
        + 2 * 3072 * 8 * 128 + 3 * 3072 * 8192), rel=0.05)


def test_plan_beats_or_matches_serial_and_verifies():
    from repro.core.schedule import check_feasible

    g_secs = np.asarray([0.5, 0.4, 0.6, 0.3])
    g_bytes = np.asarray([4e9, 3e9, 5e9, 2e9])
    plan = plan_gradient_schedule(g_secs, g_bytes, LinkSpec(), time_limit=5.0)
    assert plan.t_optimal <= plan.t_serial + 1e-9
    assert plan.t_optimal <= plan.t_greedy + 1e-9
    assert plan.gain_vs_serial >= 0.0
    # channel assignment covers every bucket
    assert plan.channel_of_bucket.shape == (4,)


def test_plan_uses_aux_channels_under_contention():
    # Tiny compute, huge transfers, slow wired share: aux channels must win.
    g_secs = np.full(4, 0.01)
    g_bytes = np.full(4, 10e9)
    no_aux = plan_gradient_schedule(
        g_secs, g_bytes, LinkSpec(ici_share=5e9, aux_channels=0), time_limit=5.0
    )
    with_aux = plan_gradient_schedule(
        g_secs, g_bytes, LinkSpec(ici_share=5e9, aux_channels=3, aux_rate=5e9),
        time_limit=5.0,
    )
    assert with_aux.t_optimal < no_aux.t_optimal * 0.6  # ~4x parallel channels
    assert (with_aux.channel_of_bucket >= 2).any()  # aux actually used


def test_replan_degradation_monotone():
    g_secs = np.asarray([0.5, 0.5, 0.5, 0.5])
    g_bytes = np.asarray([2e9, 2e9, 2e9, 2e9])
    healthy = replan(g_secs, g_bytes, LinkSpec())
    slow = replan(g_secs, g_bytes, LinkSpec(), compute_slowdown=2.0)
    fewer = replan(g_secs, g_bytes, LinkSpec(), degraded_aux=0)
    assert slow.t_optimal >= healthy.t_optimal
    assert fewer.t_optimal >= healthy.t_optimal - 1e-9
