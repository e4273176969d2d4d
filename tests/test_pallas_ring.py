"""Pallas ring collectives over async remote DMA (RDMA-over-ICI analog,
SURVEY.md §2.3/§5), run under TPU interpret mode on the CPU emulator rung —
including a race-detector pass (a capability beyond the reference's
"no formal race detection")."""
import numpy as np
import pytest

from accl_tpu import Algorithm, dataType, reduceFunction
from accl_tpu.parallel import pallas_ring

# the whole module simulates cross-device RDMA in interpret mode

WORLD = 8


def _put(accl, arr):
    import jax
    comm = accl.global_comm()
    return jax.device_put(arr, comm.sharding())


def test_pallas_ring_allgather(accl, rng):
    comm = accl.global_comm()
    x = rng.standard_normal((WORLD, 40)).astype(np.float32)
    prog = pallas_ring.build_pallas_ring_allgather(comm, dataType.float32)
    out = np.asarray(prog(_put(accl, x)))
    for r in range(WORLD):
        np.testing.assert_allclose(out[r].reshape(WORLD, 40), x, rtol=1e-6)


@pytest.mark.parametrize("func", [reduceFunction.SUM, reduceFunction.MAX])
def test_pallas_ring_reduce_scatter(accl, rng, func):
    comm = accl.global_comm()
    x = rng.standard_normal((WORLD, WORLD * 24)).astype(np.float32)
    prog = pallas_ring.build_pallas_ring_reduce_scatter(
        comm, func, dataType.float32)
    out = np.asarray(prog(_put(accl, x)))
    chunks = x.reshape(WORLD, WORLD, 24)
    ref = chunks.sum(0) if func == reduceFunction.SUM else chunks.max(0)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n", [50, 128, 1000])
def test_pallas_ring_allreduce(accl, rng, n):
    comm = accl.global_comm()
    x = rng.standard_normal((WORLD, n)).astype(np.float32)
    prog = pallas_ring.build_pallas_ring_allreduce(
        comm, reduceFunction.SUM, dataType.float32)
    out = np.asarray(prog(_put(accl, x)))
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], x.sum(0), rtol=1e-4, atol=1e-5)


def test_pallas_allreduce_through_host_api(accl, rng):
    send = accl.create_buffer(64, dataType.float32)
    recv = accl.create_buffer(64, dataType.float32)
    send.host[:] = rng.standard_normal((WORLD, 64)).astype(np.float32)
    accl.allreduce(send, recv, 64, reduceFunction.SUM,
                   algorithm=Algorithm.PALLAS)
    for r in range(WORLD):
        np.testing.assert_allclose(recv.host[r], send.host.sum(0),
                                   rtol=1e-4, atol=1e-5)


def test_pallas_reduce_scatter_allgather_through_host_api(accl, rng):
    count = 16
    send = accl.create_buffer(count * WORLD, dataType.float32)
    recv = accl.create_buffer(count, dataType.float32)
    send.host[:] = rng.standard_normal((WORLD, count * WORLD)).astype(np.float32)
    accl.reduce_scatter(send, recv, count, reduceFunction.SUM,
                        algorithm=Algorithm.PALLAS)
    full = send.host.reshape(WORLD, WORLD, count).sum(0)
    for r in range(WORLD):
        np.testing.assert_allclose(recv.host[r], full[r], rtol=1e-4, atol=1e-5)

    gsend = accl.create_buffer(count, dataType.float32)
    grecv = accl.create_buffer(count * WORLD, dataType.float32)
    gsend.host[:] = rng.standard_normal((WORLD, count)).astype(np.float32)
    accl.allgather(gsend, grecv, count, algorithm=Algorithm.PALLAS)
    for r in range(WORLD):
        np.testing.assert_allclose(
            grecv.host[r].reshape(WORLD, count), gsend.host, rtol=1e-6)


def test_pallas_kernels_race_free(accl, rng, monkeypatch):
    """Run the kernels under the interpret-mode race detector."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(
        pallas_ring, "_interpret_params",
        lambda: pltpu.InterpretParams(detect_races=True))
    comm = accl.global_comm()
    x = rng.standard_normal((WORLD, 48)).astype(np.float32)
    prog = pallas_ring.build_pallas_ring_allreduce(
        comm, reduceFunction.SUM, dataType.float32)
    out = np.asarray(prog(_put(accl, x)))
    np.testing.assert_allclose(out[0], x.sum(0), rtol=1e-4, atol=1e-5)
