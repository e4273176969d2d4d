"""Hardware rung of the test ladder: real-TPU tests (SURVEY.md §4 rungs
3-4, the axis3x / cluster analog).

Run with ``ACCL_TPU_HW=1 pytest tests/test_tpu_hardware.py`` — the env var
keeps the real TPU backend instead of the CPU emulator mesh. Tests gate
themselves on what the attached hardware provides:

* single-chip tests (Pallas plugin lanes, datapath) run on any TPU;
* multi-chip tests (Pallas ring kernels over real ICI, transport detect,
  device-initiated collectives) skip unless ≥2 chips are attached — the
  suite is ready the day multi-chip hardware appears (VERDICT round-1
  item 9); under the default CPU emulator every test here skips.
"""
import os

import numpy as np
import pytest

import jax

import accl_tpu
from accl_tpu import Algorithm, TransportBackend, dataType, reduceFunction

on_tpu = jax.default_backend() == "tpu"
n_chips = len(jax.devices()) if on_tpu else 0

tpu_only = pytest.mark.skipif(not on_tpu, reason="needs a real TPU backend")
multichip = pytest.mark.skipif(
    n_chips < 2, reason=f"needs >=2 TPU chips, have {n_chips}")


@pytest.fixture(scope="module")
def hw_accl():
    inst = accl_tpu.ACCL()
    yield inst
    inst.deinit()


# ---------------------------------------------------------------------------
# single-chip: plugin lanes + datapath on real silicon
# ---------------------------------------------------------------------------

@tpu_only
def test_pallas_reduce_lane_on_chip(hw_accl):
    """The reduce_ops Pallas lane compiles and is exact on real TPU."""
    w = hw_accl.world_size
    a = hw_accl.create_buffer(4096, dataType.float32)
    b = hw_accl.create_buffer(4096, dataType.float32)
    r = hw_accl.create_buffer(4096, dataType.float32)
    a.host[:] = np.random.randn(w, 4096).astype(np.float32)
    b.host[:] = np.random.randn(w, 4096).astype(np.float32)
    hw_accl.combine(4096, reduceFunction.SUM, a, b, r)
    np.testing.assert_allclose(r.host, a.host + b.host, rtol=1e-6)


@tpu_only
def test_pallas_compression_lane_on_chip(hw_accl):
    """The hp_compression cast lane (incl. TPU stochastic rounding path)."""
    from accl_tpu import ops
    x = jax.numpy.asarray(np.random.randn(8, 256).astype(np.float32))
    y = ops.compress(x, dataType.float32, dataType.bfloat16)
    assert y.dtype == jax.numpy.bfloat16
    z = ops.decompress(y, dataType.bfloat16, dataType.float32)
    np.testing.assert_allclose(np.asarray(z), np.asarray(x), atol=0.02,
                               rtol=0.02)


@tpu_only
def test_flash_attention_on_chip(hw_accl):
    """The fused flash-attention Pallas kernel compiled for real TPU: exact
    against the dense XLA path within mixed-precision tolerance."""
    import jax.numpy as jnp
    from accl_tpu.ops import flash
    rng = np.random.default_rng(3)
    H, S, d = 4, 1024, 128
    q, k, v = (jnp.asarray(rng.standard_normal((H, S, d)).astype(np.float32))
               for _ in range(3))
    out = np.asarray(flash.flash_attention(q, k, v, causal=True))
    sc = 1.0 / np.sqrt(d)
    s = jnp.einsum("hqd,hkd->hqk", q, k) * sc
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(mask[None], s, -jnp.inf)
    dense = np.asarray(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v))
    np.testing.assert_allclose(out, dense, rtol=5e-2, atol=1e-2)


@tpu_only
def test_transport_detected_on_chip(hw_accl):
    assert hw_accl.config.transport in (TransportBackend.ICI,
                                        TransportBackend.DCN)
    assert hw_accl.parse_hwid()["platform"] == "tpu"


# ---------------------------------------------------------------------------
# multi-chip: real-ICI skeletons (skip until >=2 chips are attached)
# ---------------------------------------------------------------------------

@multichip
@pytest.mark.parametrize("algo", [Algorithm.XLA, Algorithm.RING,
                                  Algorithm.PALLAS])
def test_allreduce_over_real_ici(hw_accl, algo):
    """Ring + Pallas allreduce over real ICI links — the collective_id,
    barrier-semaphore and LOGICAL-device-id choices in pallas_ring are
    untestable in interpret mode; this is their hardware check."""
    w = hw_accl.world_size
    s = hw_accl.create_buffer(8192, dataType.float32)
    r = hw_accl.create_buffer(8192, dataType.float32)
    s.host[:] = np.random.randn(w, 8192).astype(np.float32)
    hw_accl.allreduce(s, r, 8192, reduceFunction.SUM, algorithm=algo)
    expect = s.host.astype(np.float64).sum(0)
    for k in range(w):
        np.testing.assert_allclose(r.host[k], expect, rtol=1e-4, atol=1e-4)


@multichip
def test_chunked_pallas_allreduce_hbm_scale_on_ici(hw_accl):
    """Grid-chunked double-buffered ring kernels at HBM scale on real
    hardware (segment streaming with bounded in-flight moves)."""
    from accl_tpu.parallel import pallas_chunked
    w = hw_accl.world_size
    count = 1 << 22  # 16 MiB fp32 per rank
    comm = hw_accl.global_comm()
    prog = pallas_chunked.build_chunked_ring_allreduce(
        comm, reduceFunction.SUM, dataType.float32,
        hw_accl.config.segment_size)
    data = np.random.randn(w, count).astype(np.float32)
    x = jax.device_put(data, comm.sharding())
    out = np.asarray(prog(x))
    np.testing.assert_allclose(out[0], data.astype(np.float64).sum(0),
                               rtol=1e-3, atol=1e-3)


@multichip
def test_chunked_rooted_family_on_ici(hw_accl):
    """The segmented rooted/rotation kernels (pipelined-ring bcast,
    ring-relay scatter/gather, phased-rotation alltoall, RS+gather
    reduce) over real ICI — their role masks, per-slot send semaphores
    and global credit chains compile natively here instead of through
    the interpreter."""
    w = hw_accl.world_size
    n = 1 << 16  # 256 KiB fp32 per edge
    bcast = hw_accl.create_buffer(n, dataType.float32)
    bcast.host[:] = np.random.randn(w, n).astype(np.float32)
    rootdata = bcast.host[1].copy()
    hw_accl.bcast(bcast, n, root=1, algorithm=Algorithm.PALLAS)
    for k in range(w):
        np.testing.assert_array_equal(bcast.host[k], rootdata)

    sc_s = hw_accl.create_buffer(n * w, dataType.float32)
    sc_r = hw_accl.create_buffer(n, dataType.float32)
    sc_s.host[:] = np.random.randn(w, n * w).astype(np.float32)
    hw_accl.scatter(sc_s, sc_r, n, root=0, algorithm=Algorithm.PALLAS)
    for k in range(w):
        np.testing.assert_array_equal(
            sc_r.host[k], sc_s.host[0].reshape(w, n)[k])

    ga_r = hw_accl.create_buffer(n * w, dataType.float32)
    hw_accl.gather(sc_r, ga_r, n, root=0, algorithm=Algorithm.PALLAS)
    np.testing.assert_array_equal(
        ga_r.host[0].reshape(w, n), sc_r.host)

    a2a_r = hw_accl.create_buffer(n * w, dataType.float32)
    hw_accl.alltoall(sc_s, a2a_r, n, algorithm=Algorithm.PALLAS)
    ref = sc_s.host.reshape(w, w, n).transpose(1, 0, 2)
    np.testing.assert_array_equal(a2a_r.host, ref.reshape(w, w * n))

    rd_r = hw_accl.create_buffer(n, dataType.float32)
    hw_accl.reduce(bcast, rd_r, n, root=2, function=reduceFunction.SUM,
                   algorithm=Algorithm.PALLAS)
    np.testing.assert_allclose(
        rd_r.host[2], bcast.host.astype(np.float64).sum(0),
        rtol=1e-4, atol=1e-4)


@multichip
def test_sendrecv_over_real_ici(hw_accl):
    """Two-sided tag-matched path where the move rides a real ICI link."""
    s = hw_accl.create_buffer(1024, dataType.float32)
    r = hw_accl.create_buffer(1024, dataType.float32)
    s.host[:] = np.random.randn(hw_accl.world_size, 1024).astype(np.float32)
    hw_accl.send(s, 1024, src=0, dst=1, tag=5)
    hw_accl.recv(r, 1024, src=0, dst=1, tag=5)
    np.testing.assert_array_equal(r.host[1], s.host[0])


@multichip
def test_device_api_collective_in_kernel_on_ici(hw_accl):
    """Device-initiated collective (vadd_put analog) on real chips."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from accl_tpu import device_api as dapi

    comm = hw_accl.global_comm()
    w = comm.world_size

    def kernel(x):
        return dapi.allreduce(x + 1.0, reduceFunction.SUM)

    prog = jax.jit(shard_map(kernel, mesh=comm.mesh, in_specs=P(dapi.AXIS),
                             out_specs=P(dapi.AXIS), check_vma=False))
    data = np.random.randn(w, 512).astype(np.float32)
    x = jax.device_put(data, comm.sharding())
    out = np.asarray(prog(x))
    np.testing.assert_allclose(out[0], (data + 1.0).sum(0), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# single-chip: CommandList buffer donation (in-place fused chains)
# ---------------------------------------------------------------------------

@tpu_only
def test_cmdlist_donation_chain_on_chip(hw_accl):
    """A cmdlist chain that reuses its result buffer runs in place
    (donated) on TPU and stays exact across re-executes."""
    w = hw_accl.world_size
    n = 512 * 512  # wide-tile geometry engages
    a = hw_accl.create_buffer(n, dataType.float32)
    b = hw_accl.create_buffer(n, dataType.float32)
    r = hw_accl.create_buffer(n, dataType.float32)
    a.host[:] = np.random.randn(w, n).astype(np.float32)
    b.host[:] = np.random.randn(w, n).astype(np.float32)
    cl = hw_accl.command_list()
    cl.combine(n, reduceFunction.SUM, a, b, r)
    cl.combine(n, reduceFunction.SUM, r, b, r)
    cl.execute()
    np.testing.assert_allclose(r.host, a.host + 2 * b.host,
                               rtol=1e-5, atol=1e-5)
    a.host[:] = np.random.randn(w, n).astype(np.float32)
    cl.execute()  # reusable-list contract survives donation
    np.testing.assert_allclose(r.host, a.host + 2 * b.host,
                               rtol=1e-5, atol=1e-5)


@tpu_only
def test_cmdlist_donation_stands_down_for_async_request(hw_accl):
    """An outstanding async Request's outputs must survive a later
    execute() — donation stands down while anything is in flight
    (round-4 review finding). The second list WRITES r without reading
    it, so r's device_view is exactly the async request's held output
    array — the donation hazard; wait() would raise on a deleted array."""
    w = hw_accl.world_size
    n = 4096
    a = hw_accl.create_buffer(n, dataType.float32)
    b = hw_accl.create_buffer(n, dataType.float32)
    r = hw_accl.create_buffer(n, dataType.float32)
    a.host[:] = np.random.randn(w, n).astype(np.float32)
    b.host[:] = np.random.randn(w, n).astype(np.float32)
    cl = hw_accl.command_list()
    cl.combine(n, reduceFunction.SUM, a, b, r)
    req = cl.execute(sync=False)
    cl2 = hw_accl.command_list()
    cl2.copy(a, r, n)      # write-only use of r: its view IS req's output
    cl2.execute()          # must NOT delete req's held outputs
    req.wait(timeout=30)   # would raise on a deleted array
    np.testing.assert_allclose(r.host, a.host, rtol=1e-6)


@tpu_only
def test_cmdlist_donation_stands_down_for_parent_and_slice(hw_accl):
    """Writing a Buffer and a PARTIAL slice of it in one list must not
    donate the parent out from under the slice's write-back (round-4
    review finding): the slice's post-execute device_store reads
    parent.data, which a donated parent slot would have deleted.
    Expected values follow the list's store order (slot writes are merged
    back per buffer after the fused program: parent store first, then the
    slice region overlays it)."""
    w = hw_accl.world_size
    n = 4096
    a = hw_accl.create_buffer(n, dataType.float32)
    b = hw_accl.create_buffer(n // 2, dataType.float32)
    a.host[:] = np.random.randn(w, n).astype(np.float32)
    b.host[:] = np.random.randn(w, n // 2).astype(np.float32)
    a0 = a.host.copy()
    half = a.slice(n // 2, n)           # partial slice: distinct view array
    cl = hw_accl.command_list()
    cl.combine(n // 2, reduceFunction.SUM, half, b, half)  # writes slice
    cl.bcast(a, n, root=0)                                 # writes parent
    cl.execute()                        # must not raise on a deleted parent
    # store order follows bind order (half, b, a): the parent's bcast
    # result is merged back LAST, replacing the slice overlay — so the
    # final parent content is the broadcast of row 0
    np.testing.assert_allclose(a.host, np.broadcast_to(a0[0], (w, n)),
                               rtol=1e-5, atol=1e-5)


@tpu_only
def test_cmdlist_execute_donate_false_preserves_held_views(hw_accl):
    """``execute(donate=False)`` (ADVICE r4 #3): a device array the user
    held from a written buffer BEFORE the execute stays readable after
    it. (With the default donate=True the old array is deleted — the
    documented in-place-chain semantics.)"""
    w = hw_accl.world_size
    n = 4096
    a = hw_accl.create_buffer(n, dataType.float32)
    r = hw_accl.create_buffer(n, dataType.float32)
    a.host[:] = np.random.randn(w, n).astype(np.float32)
    r.host[:] = 7.0
    held = r.device_view()          # user keeps a pre-execute handle
    held_copy = np.asarray(held).copy()
    cl = hw_accl.command_list()
    cl.copy(a, r, n)                # writes r without reading it
    cl.execute(donate=False)
    np.testing.assert_allclose(r.host, a.host, rtol=1e-6)
    # the old handle is still alive and unchanged
    np.testing.assert_array_equal(np.asarray(held), held_copy)


# ---------------------------------------------------------------------------
# single-chip: repeated-launch stress (VERDICT r4 weak #2 — the round-4
# driver bench died to an intermittent `UNAVAILABLE: TPU device error` at
# a warm launch of the donated combine; this shakes the lifecycle the way
# the reference's 2000-iteration stress does, stress.cpp:24-34)
# ---------------------------------------------------------------------------

@tpu_only
def test_repeated_launch_stress_donated_combine_and_cast():
    """>=200 warm launches of the donated pallas_combine and the cast
    round-trip inside fori_loop programs at mixed sizes, asserting
    results every launch. A kernel/donation lifecycle fault shows up as
    a device error or a wrong value; an infrastructure fault shows
    up here too but NOT deterministically — absence of failures across
    this many launches on multiple program shapes is the evidence that
    the round-4 event was transient infra, not a kernel bug."""
    import jax.numpy as jnp
    from jax import lax
    from accl_tpu.constants import reduceFunction as rf
    from accl_tpu.ops import compression, reduce_ops

    total = int(os.environ.get("ACCL_STRESS_LAUNCHES", "200"))
    sizes = [1 << 18, 1 << 22, 1 << 24]     # 1 MiB..64 MiB f32
    k = 4
    progs = []
    for n in sizes:
        rng = np.random.default_rng(n)
        x = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
        b = jnp.asarray(np.full(n, 1e-3, np.float32))

        def combine_step(_, v, b=b):
            return reduce_ops.pallas_combine(v, b, rf.SUM, donate=True)

        def cast_step(_, v, b=b):
            w = compression.pallas_cast(v, jnp.bfloat16)
            return compression.pallas_cast(w, jnp.float32) + b

        for step, tol in ((combine_step, 1e-5), (cast_step, 4e-3)):
            prog = jax.jit(
                lambda x0, s, step=step: lax.fori_loop(
                    0, k, step, x0 + s)[:4])
            progs.append((prog, x, float(x[0]), tol))
    launches = 0
    i = 0
    while launches < total:
        prog, x, x0_head, tol = progs[i % len(progs)]
        i += 1
        s = np.float32(i * 1e-3)
        out = np.asarray(jax.block_until_ready(prog(x, s)))
        # x0 + s + k drift-adds of 1e-3 (cast path rounds through bf16)
        expect = x0_head + float(s) + k * 1e-3
        assert abs(out[0] - expect) < tol + 0.02 * abs(expect), (
            f"launch {launches}: head {out[0]} != {expect}")
        launches += 1
