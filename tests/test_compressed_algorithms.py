"""Compressed wire (ETH_COMPRESSED) × explicit algorithm families ×
uneven counts — the reference's compressed matrix crossed with the
algorithm inventory. Every hop of every family must apply the per-hop
compress/decompress lanes; int-exact checks where rounding cannot occur,
tolerance checks for bf16/f16 float wires."""
import numpy as np
import pytest

from accl_tpu import Algorithm, dataType, reduceFunction

WORLD = 8
# small ints survive bf16/f16 wire casts exactly (|x| < 256 integer grid)
_INT_RANGE = (-100, 100)


def _small_ints(rng, shape):
    return rng.integers(*_INT_RANGE, shape).astype(np.float32)


@pytest.mark.parametrize("algo", [Algorithm.RING, Algorithm.TREE,
                                  Algorithm.FLAT, Algorithm.PALLAS])
@pytest.mark.parametrize("wire", [dataType.bfloat16, dataType.float16])
@pytest.mark.parametrize("count", [33, 1021])
def test_bcast_compressed_algorithms(accl, rng, algo, wire, count):
    buf = accl.create_buffer(count, dataType.float32)
    buf.host[:] = _small_ints(rng, (WORLD, count))
    expect = buf.host[3].copy()
    accl.bcast(buf, count, 3, compress_dtype=wire, algorithm=algo)
    # small-int payloads are exact through any number of cast hops
    np.testing.assert_array_equal(buf.host, np.tile(expect, (WORLD, 1)))


@pytest.mark.parametrize("algo", [Algorithm.RING, Algorithm.TREE,
                                  Algorithm.FLAT, Algorithm.PALLAS])
@pytest.mark.parametrize("func", [reduceFunction.SUM, reduceFunction.MAX])
def test_reduce_compressed_algorithms(accl, rng, algo, func):
    count = 47
    send = accl.create_buffer(count, dataType.float32)
    recv = accl.create_buffer(count, dataType.float32)
    send.host[:] = rng.integers(-10, 10, (WORLD, count)).astype(np.float32)
    accl.reduce(send, recv, count, 2, func,
                compress_dtype=dataType.bfloat16, algorithm=algo)
    expect = (send.host.sum(0) if func == reduceFunction.SUM
              else send.host.max(0))
    # sums of small ints stay on the bf16 integer grid -> exact
    np.testing.assert_array_equal(recv.host[2], expect)


@pytest.mark.parametrize("algo", [Algorithm.RING, Algorithm.TREE,
                                  Algorithm.FLAT, Algorithm.HIERARCHICAL])
def test_allreduce_compressed_algorithms(accl, rng, algo):
    count = 96
    send = accl.create_buffer(count, dataType.float32)
    recv = accl.create_buffer(count, dataType.float32)
    send.host[:] = rng.integers(-10, 10, (WORLD, count)).astype(np.float32)
    accl.allreduce(send, recv, count, reduceFunction.SUM,
                   compress_dtype=dataType.bfloat16, algorithm=algo)
    expect = send.host.sum(0)
    for r in range(WORLD):
        np.testing.assert_array_equal(recv.host[r], expect)


@pytest.mark.parametrize("algo", [Algorithm.FLAT, Algorithm.RING,
                                  Algorithm.PALLAS])
def test_gather_compressed_algorithms(accl, rng, algo):
    count = 19
    send = accl.create_buffer(count, dataType.float32)
    recv = accl.create_buffer(count * WORLD, dataType.float32)
    send.host[:] = _small_ints(rng, (WORLD, count))
    accl.gather(send, recv, count, 5, compress_dtype=dataType.float16,
                algorithm=algo)
    np.testing.assert_array_equal(recv.host[5], send.host.reshape(-1))


def test_scatter_alltoall_compressed_flat(accl, rng):
    count = 13
    s = accl.create_buffer(count * WORLD, dataType.float32)
    r = accl.create_buffer(count, dataType.float32)
    s.host[:] = _small_ints(rng, (WORLD, count * WORLD))
    accl.scatter(s, r, count, 4, compress_dtype=dataType.bfloat16,
                 algorithm=Algorithm.FLAT)
    for k in range(WORLD):
        np.testing.assert_array_equal(
            r.host[k], s.host[4, k * count:(k + 1) * count])
    a = accl.create_buffer(count * WORLD, dataType.float32)
    ar = accl.create_buffer(count * WORLD, dataType.float32)
    a.host[:] = _small_ints(rng, (WORLD, count * WORLD))
    accl.alltoall(a, ar, count, compress_dtype=dataType.bfloat16,
                  algorithm=Algorithm.FLAT)
    for k in range(WORLD):
        expect = np.concatenate(
            [a.host[src, k * count:(k + 1) * count] for src in range(WORLD)])
        np.testing.assert_array_equal(ar.host[k], expect)


def test_scatter_alltoall_compressed_pallas(accl, rng):
    """The segmented relay/rotation kernels through the same compressed
    matrix as the FLAT family (small-int payloads are exact through any
    number of cast hops)."""
    count = 13
    s = accl.create_buffer(count * WORLD, dataType.float32)
    r = accl.create_buffer(count, dataType.float32)
    s.host[:] = _small_ints(rng, (WORLD, count * WORLD))
    accl.scatter(s, r, count, 4, compress_dtype=dataType.bfloat16,
                 algorithm=Algorithm.PALLAS)
    for k in range(WORLD):
        np.testing.assert_array_equal(
            r.host[k], s.host[4, k * count:(k + 1) * count])
    a = accl.create_buffer(count * WORLD, dataType.float32)
    ar = accl.create_buffer(count * WORLD, dataType.float32)
    a.host[:] = _small_ints(rng, (WORLD, count * WORLD))
    accl.alltoall(a, ar, count, compress_dtype=dataType.bfloat16,
                  algorithm=Algorithm.PALLAS)
    for k in range(WORLD):
        expect = np.concatenate(
            [a.host[src, k * count:(k + 1) * count] for src in range(WORLD)])
        np.testing.assert_array_equal(ar.host[k], expect)


def test_true_float_compressed_tolerance(accl, rng):
    """Real float payloads: per-hop bf16 rounding compounds with hop count;
    the result stays within the expected envelope for every family."""
    count = 64
    send = accl.create_buffer(count, dataType.float32)
    recv = accl.create_buffer(count, dataType.float32)
    send.host[:] = rng.standard_normal((WORLD, count)).astype(np.float32)
    expect = send.host.astype(np.float64).sum(0)
    for algo in (Algorithm.RING, Algorithm.TREE, Algorithm.FLAT):
        accl.allreduce(send, recv, count, reduceFunction.SUM,
                       compress_dtype=dataType.bfloat16, algorithm=algo)
        np.testing.assert_allclose(recv.host[0], expect, rtol=0.1, atol=1.0)


@pytest.mark.parametrize("wire", [dataType.bfloat16, dataType.float16])
def test_allreduce_compressed_pallas(accl, rng, wire):
    """The Pallas RDMA-over-ICI kernels run the wire lanes IN-KERNEL:
    compress in the send slot, decompress before the fold (per-hop
    ETH_COMPRESSED through the perf core — round-3 addition; round 2
    rejected compression here outright)."""
    count = 96
    send = accl.create_buffer(count, dataType.float32)
    recv = accl.create_buffer(count, dataType.float32)
    send.host[:] = rng.integers(-10, 10, (WORLD, count)).astype(np.float32)
    accl.allreduce(send, recv, count, reduceFunction.SUM,
                   compress_dtype=wire, algorithm=Algorithm.PALLAS)
    expect = send.host.sum(0)
    for r in range(WORLD):
        np.testing.assert_array_equal(recv.host[r], expect)


def test_rs_ag_compressed_pallas(accl, rng):
    count = 64
    s = accl.create_buffer(count * WORLD, dataType.float32)
    r = accl.create_buffer(count, dataType.float32)
    s.host[:] = rng.integers(-10, 10, (WORLD, count * WORLD)).astype(np.float32)
    accl.reduce_scatter(s, r, count, reduceFunction.SUM,
                        compress_dtype=dataType.bfloat16,
                        algorithm=Algorithm.PALLAS)
    expect = s.host.reshape(WORLD, WORLD, count).sum(0)
    for k in range(WORLD):
        np.testing.assert_array_equal(r.host[k], expect[k])
    sg = accl.create_buffer(count, dataType.float32)
    rg = accl.create_buffer(count * WORLD, dataType.float32)
    sg.host[:] = _small_ints(rng, (WORLD, count))
    accl.allgather(sg, rg, count, compress_dtype=dataType.float16,
                   algorithm=Algorithm.PALLAS)
    for k in range(WORLD):
        np.testing.assert_array_equal(rg.host[k], sg.host.reshape(-1))


def test_quantized_int8_wire_pallas(accl, rng):
    """Quantized int8 wire (scaled, decompress-before-arith) through the
    Pallas ring — the TPU-native extension riding the perf core."""
    from accl_tpu import ArithConfig
    pair = (dataType.float32, dataType.int8)
    accl.write_arithconfig(ArithConfig(
        *pair, quant_scale=0.5, arith_is_compressed=False))
    try:
        count = 100
        s = accl.create_buffer(count, dataType.float32)
        r = accl.create_buffer(count, dataType.float32)
        s.host[:] = (rng.integers(-3, 3, (WORLD, count)).astype(np.float32)
                     * 2.0)
        accl.allreduce(s, r, count, reduceFunction.SUM,
                       compress_dtype=dataType.int8,
                       algorithm=Algorithm.PALLAS)
        np.testing.assert_allclose(r.host[0], s.host.sum(0), atol=1e-5)
    finally:
        # the session fixture outlives this test: leave no registered pair
        # behind (test_quantized_wire asserts int8 starts unregistered)
        accl._arith_configs.pop(pair, None)
