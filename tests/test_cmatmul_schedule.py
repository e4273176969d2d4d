"""Multi-host AOT lowering proof for the collective-matmul kernels.

Mirrors ``test_chunked_schedule.py``: every overlapped builder (uni- and
bidirectional) AOT-compiles against a real ``v5e:2x4`` TPU topology —
8 chips, 2 hosts. A successful compile means Mosaic accepted the fused
ring-matmul kernels for hardware: the VMEM-resident staging (shard,
weight block, output blocks, double-buffered slots) fits, the
remote-DMA + MXU schedule lowers, and XLA scheduled the surrounding
module for a 2-host mesh. Each compile is pinned to the plan geometry
the policy chose for its shapes, so a padding/budget change is a
visible diff rather than a silicon surprise.
"""
import jax
import jax.numpy as jnp
import pytest

from accl_tpu import Algorithm
from accl_tpu.communicator import Communicator
from accl_tpu.ops import collective_matmul as cm
from accl_tpu.parallel import algorithms, pallas_ring
from conftest import assert_aot_lowered, aot_topology_devices

WORLD = 8
M, K, N = 256, 512, 512   # per-rank shard (M, K); weight block (K, N)


@pytest.fixture(scope="module")
def tpu_comm():
    devices = aot_topology_devices("v5e:2x4")
    assert len(devices) == WORLD
    comm = Communicator(devices)
    assert comm.is_multiprocess
    return comm


def _aot_compile(fn, comm, *shapes, dtype=jnp.float32):
    sh = comm.sharding()
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sh) for s in shapes]
    with jax.enable_x64(False), pallas_ring.aot_lowering():
        compiled = fn.lower(*args).compile()
    return compiled


@pytest.mark.parametrize("bidir", [False, True])
def test_agmm_lowers_multihost(tpu_comm, bidir):
    plan = cm.agmm_plan(M, K, N, WORLD, jnp.float32, bidir)
    # geometry pin: tile-aligned shapes stage unpadded, the fused output
    # panel (P, M, N) dominates the VMEM plan
    assert (plan["mp"], plan["kp"], plan["np"]) == (M, K, N)
    assert plan["nchan"] == (2 if bidir else 1)
    assert plan["vmem_bytes"] <= cm._VMEM_BUDGET
    fn = algorithms.build_allgather_matmul(
        tpu_comm, Algorithm.PALLAS, bidirectional=bidir)
    compiled = _aot_compile(fn, tpu_comm, (WORLD, M, K), (WORLD, K, N))
    assert_aot_lowered(compiled, 1)


@pytest.mark.parametrize("bidir", [False, True])
def test_mmrs_lowers_multihost(tpu_comm, bidir):
    plan = cm.mmrs_plan(WORLD * M, K, N, WORLD, jnp.float32, bidir)
    assert plan is not None and plan["cp"] == M
    assert plan["nchan"] == (2 if bidir else 1)
    assert plan["vmem_bytes"] <= cm._VMEM_BUDGET
    fn = algorithms.build_matmul_reduce_scatter(
        tpu_comm, Algorithm.PALLAS, bidirectional=bidir)
    compiled = _aot_compile(fn, tpu_comm, (WORLD, WORLD * M, K),
                            (WORLD, K, N))
    assert_aot_lowered(compiled, 1)


def test_agmm_uneven_lowers_multihost(tpu_comm):
    """Uneven-divisible shapes lower through the padding path too."""
    m, k, n = 200, 384, 300
    plan = cm.agmm_plan(m, k, n, WORLD, jnp.float32, False)
    assert (plan["mp"], plan["kp"], plan["np"]) == (200, 384, 384)
    fn = algorithms.build_allgather_matmul(tpu_comm, Algorithm.PALLAS,
                                           bidirectional=False)
    compiled = _aot_compile(fn, tpu_comm, (WORLD, m, k), (WORLD, k, n))
    assert_aot_lowered(compiled, 1)


def test_mlp_train_step_lowers_multihost():
    """The flagship workload end to end: the overlapped train step (fwd
    collective matmuls + their dual backward kernels + the round-9
    fused dw wgrads) AOT-compiles for a (2, 4) dp x tp mesh on the
    2-host topology — five fused kernels in one program."""
    from accl_tpu.models import mlp

    devices = aot_topology_devices("v5e:2x4")
    mesh = mlp.make_mesh(devices, dp=2, tp=4)
    d, h, b = 256, 1024, 32
    with jax.enable_x64(False), pallas_ring.aot_lowering():
        step = mlp.make_train_step(mesh, overlap=True)
        # shapes only — lower the per-device program
        from jax.sharding import NamedSharding, PartitionSpec as P
        specs = mlp.param_specs()
        params = mlp.MLPParams(
            w1=jax.ShapeDtypeStruct((d, h), jnp.float32,
                                    sharding=NamedSharding(mesh, specs.w1)),
            b1=jax.ShapeDtypeStruct((h,), jnp.float32,
                                    sharding=NamedSharding(mesh, specs.b1)),
            w2=jax.ShapeDtypeStruct((h, d), jnp.float32,
                                    sharding=NamedSharding(mesh, specs.w2)),
            b2=jax.ShapeDtypeStruct((d,), jnp.float32,
                                    sharding=NamedSharding(mesh, specs.b2)),
        )
        xs = jax.ShapeDtypeStruct(
            (2 * b, d), jnp.float32,
            sharding=NamedSharding(mesh, P(mlp.DP_AXIS, None)))
        compiled = step.lower(params, xs, xs).compile()
    # fwd agmm + fwd mmrs + the mmrs's agmm dual + 2 dw wgrads = at
    # least 5 Mosaic kernels (round 9: dw no longer an unfused gathered
    # matmul). The first agmm's mmrs dual would produce d(input): the
    # step never differentiates its data, and jax 0.9 dead-code-
    # eliminates that leg before lowering
    assert_aot_lowered(compiled, 5)


@pytest.mark.parametrize("bidir", [False, True])
def test_agmm_streaming_lowers_multihost(tpu_comm, bidir):
    """Round 9: a shape whose RESIDENT plan misses the 12 MiB budget
    (the (K, N) weight block alone is 16 MiB) lowers through the
    k-blocked STREAMING kernel — before round 9 these shapes silently
    compiled to the unfused XLA pair. The plan geometry is pinned so a
    k-block policy change is a visible diff."""
    m, k, n = 256, 8192, 512
    plan = cm.agmm_plan(m, k, n, WORLD, jnp.float32, bidir)
    assert plan is not None and plan["mode"] == "stream"
    assert plan["kb"] % 128 == 0 and plan["nkb"] == plan["kp"] // plan["kb"]
    assert plan["vmem_bytes"] <= cm._VMEM_BUDGET
    fn = algorithms.build_allgather_matmul(
        tpu_comm, Algorithm.PALLAS, bidirectional=bidir)
    compiled = _aot_compile(fn, tpu_comm, (WORLD, m, k), (WORLD, k, n))
    assert_aot_lowered(compiled, 1)


def test_mmrs_streaming_lowers_multihost(tpu_comm):
    m, k, n = 256, 8192, 512
    plan = cm.mmrs_plan(WORLD * m, k, n, WORLD, jnp.float32, True)
    assert plan is not None and plan["mode"] == "stream"
    fn = algorithms.build_matmul_reduce_scatter(
        tpu_comm, Algorithm.PALLAS, bidirectional=True)
    compiled = _aot_compile(fn, tpu_comm, (WORLD, WORLD * m, k),
                            (WORLD, k, n))
    assert_aot_lowered(compiled, 1)


def test_agmm_wire_lowers_multihost(tpu_comm):
    """bf16 wire staging lowers: the hp_compression cast lane plus the
    ring kernel whose staged slots are half the bytes."""
    plan = cm.agmm_plan(M, K, N, WORLD, jnp.float32, True,
                        wire_dtype=jnp.bfloat16)
    assert plan is not None
    fn = algorithms.build_allgather_matmul(
        tpu_comm, Algorithm.PALLAS, bidirectional=True, wire_dtype="bf16")
    compiled = _aot_compile(fn, tpu_comm, (WORLD, M, K), (WORLD, K, N))
    assert_aot_lowered(compiled, 2)


@pytest.mark.parametrize("travel_lhs", [True, False])
def test_wgrad_lowers_multihost(tpu_comm, travel_lhs):
    """The fused gathered-wgrad kernel (both orientations) lowers for
    the 2-host topology, pinned to its plan geometry."""
    from jax.sharding import PartitionSpec as P

    from accl_tpu.parallel.primitives import AXIS, _smap

    ms, ct, cl = 256, 512, 512
    plan = cm.wgrad_plan(ms, ct, cl, WORLD, jnp.float32, jnp.float32,
                         True)
    assert plan is not None and plan["vmem_bytes"] <= cm._VMEM_BUDGET

    def body(ts, ls):
        return cm.gathered_wgrad_body(
            ts[0], ls[0], axis=AXIS, overlap=True,
            travel_lhs=travel_lhs)[None]

    fn = _smap(tpu_comm, body, 2, in_specs=(P(AXIS), P(AXIS)))
    compiled = _aot_compile(fn, tpu_comm, (WORLD, ms, ct),
                            (WORLD, WORLD * ms, cl))
    assert_aot_lowered(compiled, 1)
