"""CommandList: fused multi-op sequences (hostctrl command-stream analog,
``hostctrl.cpp:22-63`` / ``accl_hls.h:82-496`` chained ACCLCommand) — one
device launch per recorded sequence, the dispatch-latency attack.
"""
import numpy as np
import pytest

from accl_tpu import ACCLError, dataType, errorCode, reduceFunction

WORLD = 8


def _ints(rng, shape):
    return rng.integers(-50, 50, shape).astype(np.int32)


def test_cmdlist_chain_matches_per_op_calls(accl, rng):
    """A fused allreduce→combine→bcast→allgather chain produces exactly what
    the per-op calls produce."""
    x = accl.create_buffer(64, dataType.int32)
    y = accl.create_buffer(64, dataType.int32)
    g = accl.create_buffer(64 * WORLD, dataType.int32)
    x0, y0 = _ints(rng, (WORLD, 64)), _ints(rng, (WORLD, 64))
    x.host[:] = x0; x.sync_to_device()
    y.host[:] = y0; y.sync_to_device()

    cl = accl.command_list()
    cl.allreduce(x, x, 64, reduceFunction.SUM)
    cl.combine(64, reduceFunction.MAX, x, y, y)
    cl.bcast(y, 64, 2)
    cl.allgather(y, g, 64)
    assert len(cl) == 4
    cl.execute()

    ar = np.tile(x0.sum(0), (WORLD, 1))
    comb = np.maximum(ar, y0)
    bc = np.tile(comb[2], (WORLD, 1))
    np.testing.assert_array_equal(x.host, ar)
    np.testing.assert_array_equal(y.host, bc)
    np.testing.assert_array_equal(g.host, np.tile(bc.reshape(-1), (WORLD, 1)))


def test_cmdlist_one_program_launch(accl, rng):
    """The whole list is ONE cached composite program; re-execution is a
    cache hit (the per-launch dispatch is paid once per sequence)."""
    x = accl.create_buffer(32, dataType.float32)
    x.host[:] = rng.standard_normal((WORLD, 32)).astype(np.float32)
    x.sync_to_device()
    cl = accl.command_list()
    cl.allreduce(x, x, 32, reduceFunction.SUM)
    cl.bcast(x, 32, 0)
    cl.execute()
    size0, hits0, _ = accl._programs.stats()
    cl.execute()
    size1, hits1, _ = accl._programs.stats()
    assert size1 == size0            # no new programs compiled
    assert hits1 > hits0             # composite came from the cache


def test_cmdlist_reduce_scatter_and_reduce(accl, rng):
    s = accl.create_buffer(16 * WORLD, dataType.int32)
    r = accl.create_buffer(16, dataType.int32)
    rr = accl.create_buffer(16, dataType.int32)
    s0 = _ints(rng, (WORLD, 16 * WORLD))
    s.host[:] = s0; s.sync_to_device()
    rr.host[:] = 0; rr.sync_to_device()
    cl = accl.command_list()
    cl.reduce_scatter(s, r, 16, reduceFunction.SUM)
    cl.reduce(r, rr, 16, 3, reduceFunction.MAX)
    cl.execute()
    rs = np.stack([s0[:, k * 16:(k + 1) * 16].sum(0) for k in range(WORLD)])
    np.testing.assert_array_equal(r.host, rs)
    np.testing.assert_array_equal(rr.host[3], rs.max(0))


def test_cmdlist_async_execute(accl, rng):
    x = accl.create_buffer(32, dataType.float32)
    x.host[:] = rng.standard_normal((WORLD, 32)).astype(np.float32)
    x.sync_to_device()
    expect = np.tile(x.host.sum(0), (WORLD, 1))
    cl = accl.command_list()
    cl.allreduce(x, x, 32, reduceFunction.SUM)
    req = cl.execute(sync=False)
    req.wait()
    np.testing.assert_allclose(np.asarray(x.device_view()), expect,
                               rtol=1e-5, atol=1e-5)


def test_cmdlist_rejects_oversized_counts_and_dummies(accl):
    x = accl.create_buffer(64, dataType.float32)
    cl = accl.command_list()
    with pytest.raises(ACCLError) as ei:
        cl.bcast(x, 128, 0)
    assert ei.value.code == errorCode.INVALID_BUFFER_SIZE
    with pytest.raises(ACCLError):
        cl.copy(accl.dummy_buffer(), x, 64)


def test_cmdlist_partial_counts(accl, rng):
    """Round-3: partial-count operands (slice plumbing between steps) —
    an op may use a prefix of its buffer; the tail is preserved
    (accl_hls.h ACCLCommand count operands)."""
    x = accl.create_buffer(64, dataType.int32)
    y = accl.create_buffer(64, dataType.int32)
    x0, y0 = _ints(rng, (WORLD, 64)), _ints(rng, (WORLD, 64))
    x.host[:] = x0
    y.host[:] = y0
    cl = accl.command_list()
    cl.allreduce(x, y, 32, reduceFunction.SUM)   # only the first 32
    cl.bcast(y, 16, root=3)                      # then first 16 from rank 3
    cl.execute()
    want = np.tile(x0[:, :32].sum(0), (WORLD, 1))
    want[:, :16] = want[3, :16]
    np.testing.assert_array_equal(y.host[:, :32], want)
    np.testing.assert_array_equal(y.host[:, 32:], y0[:, 32:])  # tail kept


def test_cmdlist_full_op_set(accl, rng):
    """scatter / gather / alltoall in a fused chain (VERDICT r2 #8: the
    reference ACCLCommand covers the full op set, accl_hls.h:82-496)."""
    n = 16
    root = 2
    s = accl.create_buffer(n * WORLD, dataType.int32)
    r = accl.create_buffer(n, dataType.int32)
    g = accl.create_buffer(n * WORLD, dataType.int32)
    a = accl.create_buffer(n * WORLD, dataType.int32)
    s0 = _ints(rng, (WORLD, n * WORLD))
    s.host[:] = s0
    cl = accl.command_list()
    cl.scatter(s, r, n, root)
    cl.gather(r, g, n, root)
    cl.alltoall(s, a, n)
    cl.execute()
    for k in range(WORLD):
        np.testing.assert_array_equal(
            r.host[k], s0[root, k * n:(k + 1) * n])
    np.testing.assert_array_equal(g.host[root], s0[root])
    for k in range(WORLD):
        expect = np.concatenate(
            [s0[src, k * n:(k + 1) * n] for src in range(WORLD)])
        np.testing.assert_array_equal(a.host[k], expect)


def test_cmdlist_send_recv_pair_fuses(accl, rng):
    """A send/recv pair inside one list executes as one fused move step,
    chained with collectives in a single launch."""
    n = 48
    x = accl.create_buffer(n, dataType.int32)
    y = accl.create_buffer(n, dataType.int32)
    x0 = _ints(rng, (WORLD, n))
    x.host[:] = x0
    cl = accl.command_list()
    cl.allreduce(x, x, n, reduceFunction.SUM)
    cl.send(x, n, src=1, dst=5, tag=9)
    cl.recv(y, n, src=1, dst=5, tag=9)
    cl.bcast(y, n, root=5)
    cl.execute()
    want = x0.sum(0)
    np.testing.assert_array_equal(x.host, np.tile(want, (WORLD, 1)))
    np.testing.assert_array_equal(y.host, np.tile(want, (WORLD, 1)))


def test_cmdlist_unpaired_send_recv_rejected(accl):
    x = accl.create_buffer(8, dataType.float32)
    cl = accl.command_list()
    cl.send(x, 8, src=0, dst=1, tag=3)
    with pytest.raises(ACCLError) as ei:
        cl.execute()
    assert ei.value.code == errorCode.CONFIG_ERROR
    cl2 = accl.command_list()
    with pytest.raises(ACCLError):
        cl2.recv(x, 8, src=0, dst=1, tag=3)  # no send recorded
    cl3 = accl.command_list()
    cl3.send(x, 8, src=0, dst=1, tag=3)
    with pytest.raises(ACCLError) as ei3:
        cl3.recv(x, 4, src=0, dst=1, tag=3)  # count mismatch
    assert ei3.value.code == errorCode.INVALID_BUFFER_SIZE


def test_cmdlist_reselects_after_autotune(accl, monkeypatch):
    """ADVICE r2 #3: a recorded list re-resolves algorithm selection at
    execute() time, so autotuned thresholds apply to existing lists."""
    from accl_tpu.config import Algorithm
    from accl_tpu.parallel import algorithms as alg
    n = 64
    x = accl.create_buffer(n, dataType.int32)
    y = accl.create_buffer(n, dataType.int32)
    x.host[:] = 1
    cl = accl.command_list()
    cl.allreduce(x, y, n, reduceFunction.SUM)
    seen = []
    orig_select = alg.select_plan

    def spy(op, nbytes, comm, cfg, requested=None, **kw):
        got, plan = orig_select(op, nbytes, comm, cfg, requested, **kw)
        seen.append((op, got))
        return got, plan

    monkeypatch.setattr(alg, "select_plan", spy)
    cl.execute()
    first = [g for o, g in seen if o.name == "allreduce"][-1]
    # shrink the ring threshold below this payload: re-execute must
    # re-select RING without re-recording
    orig_cfg = accl.config
    try:
        accl.config = accl.config.replace(ring_threshold=1)
        accl._programs.clear()
        seen.clear()
        cl.execute()
        second = [g for o, g in seen if o.name == "allreduce"][-1]
        # first: the token-sized payload rides the latency tier's flat
        # star (round 13); second: the shrunk ring_threshold is an
        # autotune seed, which pins the legacy ladder -> RING
        assert first == Algorithm.FLAT and second == Algorithm.RING
        np.testing.assert_array_equal(y.host, np.full((WORLD, n), WORLD))
    finally:
        accl.config = orig_cfg


def test_cmdlist_empty_execute_is_noop(accl):
    assert accl.command_list().execute() is None


def test_cmdlist_picks_up_host_writes_each_execute(accl, rng):
    """execute() syncs read-before-write inputs from host every time, even
    for buffers already materialized on device — same visibility rules as
    the per-op from_device=False default."""
    x = accl.create_buffer(32, dataType.int32)
    y = accl.create_buffer(32, dataType.int32)
    x.host[:] = _ints(rng, (WORLD, 32))
    accl.copy(x, y, 32)  # materializes x on device with the first values
    cl = accl.command_list()
    cl.allreduce(x, y, 32, reduceFunction.SUM)
    second = _ints(rng, (WORLD, 32))
    x.host[:] = second   # host write AFTER device materialization
    cl.execute()
    np.testing.assert_array_equal(y.host, np.tile(second.sum(0), (WORLD, 1)))


def test_cmdlist_from_device_skips_host_upload(accl, rng):
    """execute(from_device=True) is the list-wide analog of the per-op
    from_device=True knob: the device state is authoritative and a later
    host write is NOT picked up (callers assert device currency)."""
    x = accl.create_buffer(32, dataType.int32)
    y = accl.create_buffer(32, dataType.int32)
    first = _ints(rng, (WORLD, 32))
    x.host[:] = first
    cl = accl.command_list()
    cl.allreduce(x, y, 32, reduceFunction.SUM)
    cl.execute()  # uploads `first`, leaves it materialized on device
    np.testing.assert_array_equal(y.host, np.tile(first.sum(0), (WORLD, 1)))
    x.host[:] = _ints(rng, (WORLD, 32))  # host write the re-execute ignores
    cl.execute(from_device=True)
    np.testing.assert_array_equal(y.host, np.tile(first.sum(0), (WORLD, 1)))


def test_cmdlist_fuses_chunked_pallas_step(accl, rng):
    """A recorded list mixing a Pallas chunked collective with jnp-family
    steps compiles and launches as one fused program — the segmented
    kernels are ordinary steps to the CommandList because the shared
    _spec_* builders route them (accl_hls.h chained-command analog)."""
    from accl_tpu import Algorithm
    n = 2048
    x = accl.create_buffer(n, dataType.float32)
    y = accl.create_buffer(n, dataType.float32)
    x.host[:] = rng.standard_normal((WORLD, n)).astype(np.float32)
    rootdata = x.host[2].copy()
    cl = accl.command_list()
    cl.bcast(x, n, root=2, algorithm=Algorithm.PALLAS)
    cl.allreduce(x, y, n, reduceFunction.SUM)
    cl.execute()
    np.testing.assert_array_equal(x.host, np.tile(rootdata, (WORLD, 1)))
    np.testing.assert_allclose(
        y.host, np.tile(rootdata * WORLD, (WORLD, 1)), rtol=1e-5)
