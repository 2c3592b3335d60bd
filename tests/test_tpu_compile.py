"""Compile the served path's device programs for a TPU v5e that is described,
not attached: the chip's own compiler refuses what interpret mode hides
(blocks past the scoped VMEM, tilings Mosaic cannot lower).

The only file that describes the topology; it does so inside a fixture, so
every pytest worker collects the same tests and only the one running this
file loads the TPU compiler.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import vectorized as V
from repro.core.instance import ProblemInstance, Topology
from repro.kernels import cpm
from repro.kernels import ops as kops
from repro.launch.hlo_analysis import _called, _split_computations
from repro.online import DEFAULT_SOLVER_KWARGS, production_arrivals

BATCH = DEFAULT_SOLVER_KWARGS["batch_size"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: not describable here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def production_fleet(n_jobs: int) -> list[ProblemInstance]:
    """§V production jobs on the stress cluster; odd ones with a restricted
    topology so the fleet's stage 1 takes the masked kernel."""
    rng = np.random.default_rng(0)
    out = []
    arrivals = production_arrivals(0, 1.0, n_jobs, n_racks=8, n_wireless=2)
    for i, inst in enumerate(ev.inst for ev in arrivals):
        if i % 2:
            reach = rng.random((inst.n_racks, inst.n_wireless)) < 0.5
            inst = ProblemInstance(
                job=inst.job, n_racks=inst.n_racks, n_wireless=inst.n_wireless,
                topology=Topology(reach=reach),
            )
        out.append(inst)
    return out


# The engine's buckets for the §V mix (n_pad 8 or 16) at one job and at the
# serving width of 8 jobs, both kernel variants; plus the largest bucket
# once, with the masked kernel (two block-sized inputs).
KERNEL_CASES = [
    (n_pad, I, masked)
    for n_pad in (8, 16)
    for I in (1, 8)
    for masked in (False, True)
] + [(128, 1, True)]


@pytest.mark.parametrize(
    "n_pad,I,masked",
    KERNEL_CASES,
    ids=[f"n{n}-I{i}-{'masked' if m else 'plain'}" for n, i, m in KERNEL_CASES],
)
def test_bound_kernel_compiles_with_derived_block(one_chip, n_pad, I, masked):
    B = I * BATCH
    args = [
        spec((B, n_pad, n_pad), jnp.float32, one_chip),
        spec((B, n_pad), jnp.float32, one_chip),
        spec((B,), jnp.float32, one_chip),
    ]
    if masked:
        args.append(spec((B, n_pad, n_pad), jnp.float32, one_chip))

    def stage1(w, p, extra, mask=None):
        return cpm.batched_combined_lb(
            w, p, extra, mask=mask, n_iters=n_pad - 1, interpret=False
        )

    compiled = jax.jit(stage1).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    bb = cpm.block_rows(B, n_pad)
    assert bb == B or (bb % 8 == 0 and B % bb == 0)


@pytest.mark.parametrize("topology", [False, True], ids=["plain", "masked"])
def test_engine_stage1_program_compiles(one_chip, monkeypatch, topology):
    """The whole stage-1 program of an 8-job production fleet, as the engine
    builds it (adjacency scatter, contention terms, fused kernel)."""
    fleet = production_fleet(8)
    if not topology:
        fleet = [
            ProblemInstance(job=x.job, n_racks=x.n_racks, n_wireless=x.n_wireless)
            for x in fleet
        ]
    dims = V._fleet_dims(fleet, True)
    assert dims.n_pad == 16
    B = len(fleet) * BATCH
    arrays = V._build_lb_arrays(fleet, dims)
    args = [
        spec((B, dims.n_pad), jnp.int32, one_chip),
        spec((B,), jnp.int32, one_chip),
    ] + [spec(a.shape, a.dtype, one_chip) for a in arrays]
    # The kernel wrapper picks interpret mode from the default backend (the
    # CPU here); compile as on the chip. A fresh jit of the unwrapped program
    # keeps this trace out of the engine's own cache.
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    program = functools.partial(
        V._fleet_lb_device.__wrapped__,
        M_pad=dims.M_pad, n_iters=dims.n_iters, contention=True,
    )
    compiled = jax.jit(program).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stage2_evaluator_compiles(one_chip):
    """The stage-2 scan evaluator at the serving width: 8 jobs x 512 rows."""
    fleet = production_fleet(8)
    ops = [V.build_op_tables(x) for x in fleet]
    dims = V._fleet_dims(fleet, True, ops)
    tables = V._build_eval_stack(fleet, dims, True, ops)
    B = len(fleet) * BATCH
    args = [
        spec((B, dims.n_pad), jnp.int32, one_chip),
        spec((B,), jnp.int32, one_chip),
    ] + [spec(t.shape, t.dtype, one_chip) for t in tables]
    fn = V._compiled_evaluator(1, dims.m_pad, dims.M_pad, dims.n_chan)
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_stage2_scan_body_has_no_gather(one_chip):
    """At the widest production bucket (16 jobs x 512 rows, n_ops 64, m_pad
    32, indeg_pad 16) the scan step reads its carry and tables through
    one-hot selects: no gather is reachable from the ``while`` body."""
    fleet = production_fleet(16)
    ops = [V.build_op_tables(x) for x in fleet]
    dims = V._fleet_dims(fleet, True, ops)
    assert (dims.n_ops, dims.m_pad, dims.indeg_pad) == (64, 32, 16)
    tables = V._build_eval_stack(fleet, dims, True, ops)
    B = len(fleet) * BATCH
    args = [
        spec((B, dims.n_pad), jnp.int32, one_chip),
        spec((B,), jnp.int32, one_chip),
    ] + [spec(t.shape, t.dtype, one_chip) for t in tables]
    fn = V._compiled_evaluator(1, dims.m_pad, dims.M_pad, dims.n_chan)
    comps = _split_computations(fn.lower(*args).compile().as_text())
    loops = [i for c in comps.values() for i in c if i.op == "while"]
    assert loops
    seen, todo = set(), [c for i in loops for c in _called(i)]
    while todo:
        name = todo.pop()
        if name in comps and name not in seen:
            seen.add(name)
            todo += [c for i in comps[name] for c in _called(i)]
    ops_in_loop = [i.op for name in seen for i in comps[name]]
    assert "fusion" in ops_in_loop
    assert "gather" not in ops_in_loop
