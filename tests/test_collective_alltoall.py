"""Fused all-to-all × expert matmul (ops/collective_alltoall.py): the
MoE dispatch/combine datapath with the wire hidden under expert compute.

Parity is BIT-exact fp32 against the unfused ``lax.all_to_all`` + einsum
pair: operands are integer-valued floats (every product and partial sum
is exactly representable), so any reassociation the exchange schedule
introduces cannot hide behind tolerance. Kernel suites run under the
TPU interpreter's simulated remote DMA; the policy/plan/fallback/moe
tests check that the entry points resolve to the unfused pair where a
plan declines, same math by construction.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accl_tpu import Algorithm
from accl_tpu.communicator import Communicator
from accl_tpu.ops import collective_alltoall as ca
from accl_tpu.ops import collective_matmul as cm
from accl_tpu.parallel import algorithms, pallas_ring

WORLD = 8


def _ints(rng, shape, lo=-4, hi=5):
    """Integer-valued fp32: exact under any summation order."""
    return rng.integers(lo, hi, shape).astype(np.float32)


def _comm(W):
    return Communicator(jax.devices()[:W])


def _put(comm, arr):
    return jax.device_put(arr, comm.sharding())


def _run_a2amm(comm, x, w, algo, bidirectional, wire_dtype=None):
    prog = algorithms.build_alltoall_matmul(
        comm, algo, bidirectional=bidirectional, wire_dtype=wire_dtype)
    return np.asarray(prog(_put(comm, x), _put(comm, w)))


def _run_mma2a(comm, h, w, algo, bidirectional, wire_dtype=None):
    prog = algorithms.build_matmul_alltoall(
        comm, algo, bidirectional=bidirectional, wire_dtype=wire_dtype)
    return np.asarray(prog(_put(comm, h), _put(comm, w)))


def _host_dispatch(x, w):
    """out[r, e] = concat_s(x[s, r-block e]) @ w[r, e] — the oracle."""
    W, E, C, d = x.shape
    el, h = w.shape[1], w.shape[3]
    out = np.zeros((W, el, W * C, h), np.float64)
    for r in range(W):
        for e in range(el):
            recv = np.concatenate(
                [x[s, r * el + e] for s in range(W)], axis=0)  # (W*C, d)
            out[r, e] = recv.astype(np.float64) @ w[r, e].astype(np.float64)
    return out


def _host_combine(h, w):
    """out[r] = stack_s(y_s[:, r-block]) with y_s[e] = h[s, e] @ w[s, e]."""
    W, el, PC, hd = h.shape
    d = w.shape[3]
    C = PC // W
    y = np.einsum("reph,rehd->repd", h.astype(np.float64),
                  w.astype(np.float64))
    out = np.zeros((W, W * el, C, d), np.float64)
    for r in range(W):
        for s in range(W):
            out[r, s * el:(s + 1) * el] = y[s, :, r * C:(r + 1) * C, :]
    return out


# ---------------------------------------------------------------------------
# interpreter parity: fused kernels vs the unfused pair, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W", [2, 4, 8])
@pytest.mark.parametrize("shape", [(2, 8, 128, 128),   # dense, tile-aligned
                                   (2, 5, 72, 40)])    # uneven, padded
def test_a2amm_parity_bit_exact(accl, rng, W, shape):
    el, C, d, h = shape
    x = _ints(rng, (W, W * el, C, d))
    w = _ints(rng, (W, el, d, h))
    comm = _comm(W)
    fused = _run_a2amm(comm, x, w, Algorithm.PALLAS, bidirectional=False)
    ref = _run_a2amm(comm, x, w, Algorithm.XLA, bidirectional=False)
    np.testing.assert_array_equal(fused, ref)
    np.testing.assert_array_equal(
        fused, _host_dispatch(x, w).astype(np.float32))


@pytest.mark.parametrize("W", [4, 8])
@pytest.mark.parametrize("shape", [(2, 8, 128, 128), (2, 5, 72, 40)])
def test_a2amm_parity_bidirectional(accl, rng, W, shape):
    """The counter-rotating channels (P >= 4: channel 1 exchanges at
    negative distances) are output-identical to the unidirectional
    schedule and the XLA pair."""
    el, C, d, h = shape
    x = _ints(rng, (W, W * el, C, d))
    w = _ints(rng, (W, el, d, h))
    comm = _comm(W)
    fused = _run_a2amm(comm, x, w, Algorithm.PALLAS, bidirectional=True)
    ref = _run_a2amm(comm, x, w, Algorithm.XLA, bidirectional=True)
    np.testing.assert_array_equal(fused, ref)


@pytest.mark.parametrize("W", [2, 4, 8])
@pytest.mark.parametrize("shape", [(2, 8, 128, 128), (2, 5, 72, 40)])
def test_mma2a_parity_bit_exact(accl, rng, W, shape):
    el, C, d, h = shape
    hx = _ints(rng, (W, el, W * C, h), lo=-3, hi=4)
    w = _ints(rng, (W, el, h, d), lo=-3, hi=4)
    comm = _comm(W)
    fused = _run_mma2a(comm, hx, w, Algorithm.PALLAS, bidirectional=False)
    ref = _run_mma2a(comm, hx, w, Algorithm.XLA, bidirectional=False)
    np.testing.assert_array_equal(fused, ref)
    np.testing.assert_array_equal(
        fused, _host_combine(hx, w).astype(np.float32))


@pytest.mark.parametrize("W", [4, 8])
@pytest.mark.parametrize("shape", [(2, 8, 128, 128), (2, 5, 72, 40)])
def test_mma2a_parity_bidirectional(accl, rng, W, shape):
    el, C, d, h = shape
    hx = _ints(rng, (W, el, W * C, h), lo=-3, hi=4)
    w = _ints(rng, (W, el, h, d), lo=-3, hi=4)
    comm = _comm(W)
    fused = _run_mma2a(comm, hx, w, Algorithm.PALLAS, bidirectional=True)
    ref = _run_mma2a(comm, hx, w, Algorithm.XLA, bidirectional=True)
    np.testing.assert_array_equal(fused, ref)


def test_a2a_race_free(accl, rng, monkeypatch):
    """Both flat-exchange kernels, uni- and bidirectional, under the
    interpret-mode race detector: the dispatch credit protocol (grants
    == gates) and the combine's write-once output discipline must hold
    with the MXU folded into the schedule."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(
        pallas_ring, "_interpret_params",
        lambda: pltpu.InterpretParams(detect_races=True))
    comm = _comm(WORLD)
    el, C, d, h = 2, 8, 128, 128
    x = _ints(rng, (WORLD, WORLD * el, C, d))
    hx = _ints(rng, (WORLD, el, WORLD * C, h), lo=-3, hi=4)
    w_in = _ints(rng, (WORLD, el, d, h), lo=-3, hi=4)
    w_out = _ints(rng, (WORLD, el, h, d), lo=-3, hi=4)
    for bidir in (False, True):
        fused = _run_a2amm(comm, x, w_in, Algorithm.PALLAS, bidir)
        np.testing.assert_array_equal(
            fused, _run_a2amm(comm, x, w_in, Algorithm.XLA, bidir))
        fused = _run_mma2a(comm, hx, w_out, Algorithm.PALLAS, bidir)
        np.testing.assert_array_equal(
            fused, _run_mma2a(comm, hx, w_out, Algorithm.XLA, bidir))


def test_a2a_grads_through_kernels(accl, rng):
    """The custom VJPs (each kernel's backward dx is the other kernel)
    match the grads of the unfused pair — same integer-exactness."""
    from jax.sharding import PartitionSpec as P

    from accl_tpu.parallel.primitives import AXIS, _smap

    comm = _comm(4)
    W, el, C, d, h = 4, 2, 8, 64, 32
    x = _ints(rng, (W, W * el, C, d), lo=-2, hi=3)
    w_in = _ints(rng, (W, el, d, h), lo=-2, hi=3)
    w_out = _ints(rng, (W, el, h, d), lo=-2, hi=3)

    def make(overlap):
        def body(xs, wi, wo):
            def loss(args):
                wi_, wo_ = args
                a = ca.alltoall_matmul(xs[0], wi_, AXIS, None, overlap)
                z = ca.matmul_alltoall(a.astype(xs.dtype), wo_, AXIS,
                                       None, overlap)
                return jnp.sum(z)

            gi, go = jax.grad(loss)((wi[0], wo[0]))
            return gi[None], go[None]

        return _smap(comm, body, 3,
                     in_specs=(P(AXIS), P(AXIS), P(AXIS)),
                     out_specs=(P(AXIS), P(AXIS)))

    gi_f, go_f = make(True)(_put(comm, x), _put(comm, w_in),
                            _put(comm, w_out))
    gi_r, go_r = make(False)(_put(comm, x), _put(comm, w_in),
                             _put(comm, w_out))
    np.testing.assert_array_equal(np.asarray(gi_f), np.asarray(gi_r))
    np.testing.assert_array_equal(np.asarray(go_f), np.asarray(go_r))


def test_a2a_wire_bit_exact_with_f32_accumulate(accl, rng):
    """bf16 wire staging for dispatch rounds the token payload once:
    with small-integer operands (bf16-lossless) the wire path is
    bit-exact vs the full-precision pair while the expert matmul's
    partial sums exceed bf16's exact range — an exact result PROVES the
    accumulation ran wider than the wire."""
    W, el, C, d, h = 4, 2, 8, 512, 64
    comm = _comm(W)
    x = _ints(rng, (W, W * el, C, d), lo=-3, hi=4)
    w = _ints(rng, (W, el, d, h), lo=-3, hi=4)
    fused = _run_a2amm(comm, x, w, Algorithm.PALLAS, True,
                       wire_dtype="bf16")
    ref = _run_a2amm(comm, x, w, Algorithm.XLA, True)
    assert np.abs(ref).max() > 256      # sums overflow bf16 exactness
    np.testing.assert_array_equal(fused, ref)


def test_mma2a_wire_tolerance(accl, rng):
    """bf16 wire for combine rounds each travelling y block once (local
    block included, for uniform semantics) — tolerance-bounded vs the
    f32 pair, and exact when every block value is bf16-representable."""
    W, el, C, d, h = 4, 2, 8, 32, 64
    comm = _comm(W)
    hx = rng.standard_normal((W, el, W * C, h)).astype(np.float32)
    w = rng.standard_normal((W, el, h, d)).astype(np.float32)
    fused = _run_mma2a(comm, hx, w, Algorithm.PALLAS, True,
                       wire_dtype="bf16")
    ref = _run_mma2a(comm, hx, w, Algorithm.XLA, True)
    # ONE bf16 rounding per element on the block scale
    np.testing.assert_allclose(fused, ref, rtol=0.02,
                               atol=0.02 * np.abs(ref).max())
    # tiny integers: every block value stays bf16-exact
    hi = _ints(rng, (W, el, W * 8, 8), lo=-1, hi=2)
    wi = _ints(rng, (W, el, 8, d), lo=-1, hi=2)
    fused = _run_mma2a(comm, hi, wi, Algorithm.PALLAS, False,
                       wire_dtype="bf16")
    ref = _run_mma2a(comm, hi, wi, Algorithm.XLA, False)
    np.testing.assert_array_equal(fused, ref)


def test_moe_fused_matches_baseline_kernels(accl, rng):
    """The flagship consumer on the kernel rung: build_moe_forward with
    the fused datapath engaged matches the lax baseline to float
    tolerance (routing/softmax values are not integer, so reassociation
    tolerance applies — the kernels themselves are pinned bit-exact
    above)."""
    from accl_tpu.models import moe

    comm = _comm(4)
    W, n, d, E, C = 4, 16, 128, 8, 8
    gp = moe.init_params(jax.random.PRNGKey(0), comm, d, 128, E)
    params = moe.shard_params(gp, comm)
    x = rng.standard_normal((W, n, d)).astype(np.float32)
    xg = _put(comm, x)
    base = np.asarray(
        moe.build_moe_forward(comm, E, C, overlap=False)(params, xg))
    el = E // W
    assert ca.a2a_matmul_engages(el, C, d, 128, W, jnp.float32, True)
    fused = np.asarray(
        moe.build_moe_forward(comm, E, C, overlap=True)(params, xg))
    np.testing.assert_allclose(fused, base, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# block-geometry policy (every rung)
# ---------------------------------------------------------------------------

def test_a2a_plan_geometry_pins():
    """The plan is the kernel's geometry contract — pin it so a silent
    padding change shows up as a diff, not a VMEM surprise."""
    p = ca.a2a_plan(2, 5, 72, 40, 4, jnp.float32, False,
                    direction="dispatch")
    assert (p["cp"], p["dp"], p["hp"], p["nchan"]) == (8, 128, 128, 1)
    assert p["mode"] == "resident"
    p = ca.a2a_plan(2, 5, 72, 40, 4, jnp.float32, True,
                    direction="dispatch")
    assert p["nchan"] == 2                      # counter-rotating split
    p = ca.a2a_plan(2, 5, 72, 40, 2, jnp.float32, True,
                    direction="dispatch")
    assert p["nchan"] == 1                      # bidirectional needs P>=4
    p = ca.a2a_plan(2, 5, 72, 40, 4, jnp.float32, False,
                    direction="combine")
    assert (p["cp"], p["dp"], p["hp"]) == (8, 128, 128)
    # bf16 wire: capacity rows pad to 16-row sublane tiles
    p = ca.a2a_plan(2, 8, 128, 128, 4, jnp.float32, False,
                    direction="dispatch", wire_dtype=jnp.bfloat16)
    assert p["cp"] == 16
    with pytest.raises(ValueError, match="direction"):
        ca.a2a_plan(2, 8, 128, 128, 4, jnp.float32, False,
                    direction="sideways")


def test_a2a_plan_vmem_budget_fallback():
    """Geometry that misses the scoped-VMEM budget returns None — the
    unfused-lax fallback trigger (no streaming mode: MoE blocks are
    capacity-bounded by construction)."""
    assert ca.a2a_plan(8, 1024, 4096, 4096, 8, jnp.float32, False,
                       direction="dispatch") is None
    assert ca.a2a_plan(8, 1024, 4096, 4096, 8, jnp.float32, False,
                       direction="combine") is None
    ok = ca.a2a_plan(2, 64, 256, 512, 8, jnp.float32, False,
                     direction="dispatch")
    assert ok is not None and ok["vmem_bytes"] <= ca._VMEM_BUDGET
    # a wire dtype halves the staged payload terms
    full = ca.a2a_plan(2, 64, 1024, 256, 4, jnp.float32, False,
                       direction="dispatch")
    half = ca.a2a_plan(2, 64, 1024, 256, 4, jnp.float32, False,
                       direction="dispatch", wire_dtype=jnp.bfloat16)
    assert half["vmem_bytes"] < full["vmem_bytes"]


def test_chan_steps_cover_every_distance():
    """The counter-rotating channel split must cover ring distances
    1..P-1 exactly once for every world size."""
    for P in range(2, 10):
        for nchan in (1, 2):
            got = []
            for sign, T in ca._chan_steps(P, nchan):
                got += [(sign * u) % P for u in range(1, T + 1)]
            assert sorted(got) == list(range(1, P)), (P, nchan, got)


def test_a2a_session_config_write_through(accl):
    """ACCLConfig.moe_overlap / a2a_matmul_threshold land in the kernel
    module on every config assignment (the cmatmul_overlap discipline)."""
    saved = accl.config
    try:
        accl.config = accl.config.replace(moe_overlap=False)
        assert ca.get_overlap_enabled() is False
        accl.config = accl.config.replace(moe_overlap=True,
                                          a2a_matmul_threshold=12345)
        assert ca.get_overlap_enabled() is True
        assert ca.get_overlap_threshold() == 12345
    finally:
        accl.config = saved


def test_a2a_engage_resolution(accl, monkeypatch):
    """The overlap=None session default resolves the switch, the size
    register (in block WIRE bytes), the plan and the rung; an explicit
    True bypasses the register, False always declines."""
    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    el, C, d, h = 2, 8, 64, 64
    saved_ov = ca.get_overlap_enabled()
    saved_th = ca.get_overlap_threshold()
    saved_w = cm.get_wire_dtype()
    try:
        ca.set_overlap_threshold(0)
        ca.set_overlap_enabled(False)
        assert ca.a2a_matmul_engages(el, C, d, h, 4, jnp.float32) is False
        ca.set_overlap_enabled(True)
        assert ca.a2a_matmul_engages(el, C, d, h, 4, jnp.float32) is True
        assert ca.a2a_matmul_engages(el, C, d, h, 4, jnp.float32,
                                     False) is False
        # register above the block -> session default declines, the
        # explicit per-call force bypasses
        block = el * C * d * 4
        ca.set_overlap_threshold(block + 1)
        assert ca.a2a_matmul_engages(el, C, d, h, 4, jnp.float32) is False
        assert ca.a2a_matmul_engages(el, C, d, h, 4, jnp.float32,
                                     True) is True
        ca.set_overlap_threshold(block)
        assert ca.a2a_matmul_engages(el, C, d, h, 4, jnp.float32) is True
        # wire staging halves the effective bytes: the same block no
        # longer clears the f32-sized register
        cm.set_wire_dtype("bf16")
        assert ca.a2a_matmul_engages(el, C, d, h, 4, jnp.float32) is False
        # oversized plans never engage, regardless of the register
        cm.set_wire_dtype(None)
        ca.set_overlap_threshold(0)
        assert ca.a2a_matmul_engages(8, 1024, 4096, 4096, 8, jnp.float32,
                                     True) is False
    finally:
        ca.set_overlap_enabled(saved_ov)
        ca.set_overlap_threshold(saved_th)
        cm.set_wire_dtype(saved_w)


# ---------------------------------------------------------------------------
# dispatch plumbing
# ---------------------------------------------------------------------------

def test_select_a2a_operations(accl):
    """select() dispatch for the fused a2a family: the shared register
    gates both ops on ICI (in effective wire bytes), explicit requests
    win, unsupported families are rejected, off-ICI never auto-selects."""
    from accl_tpu.config import TransportBackend
    from accl_tpu.constants import operation

    comm = accl.global_comm()
    ici = accl.config.replace(transport=TransportBackend.ICI)
    th = ici.a2a_matmul_threshold
    for op in (operation.alltoall_matmul, operation.matmul_alltoall):
        # SIM transport: the kernels would measure the simulator
        assert algorithms.select(op, th, comm, accl.config) \
            == Algorithm.XLA
        assert algorithms.select(op, th, comm, ici) == Algorithm.PALLAS
        assert algorithms.select(op, th - 1, comm, ici) == Algorithm.XLA
        assert algorithms.select(op, 0, comm, ici,
                                 Algorithm.PALLAS) == Algorithm.PALLAS
        with pytest.raises(ValueError):
            algorithms.select(op, th, comm, ici, Algorithm.RING)
    # the register compares WIRE bytes under the session wire dtype
    wired = ici.replace(cmatmul_wire_dtype="bf16")
    assert algorithms.select(operation.alltoall_matmul, th, comm,
                             wired) == Algorithm.XLA
    assert algorithms.select(operation.alltoall_matmul, 2 * th, comm,
                             wired) == Algorithm.PALLAS


def test_a2a_body_rejects_bad_shapes(accl):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    def run(body, xshape, wshape):
        f = shard_map(body, mesh=mesh, in_specs=(P("accl"), P(None)),
                      out_specs=P("accl"), check_vma=False)
        return jax.make_jaxpr(f)(jnp.zeros(xshape, jnp.float32),
                                 jnp.zeros(wshape, jnp.float32))

    with pytest.raises(ValueError, match="contraction"):
        run(lambda x, w: ca.alltoall_matmul_body(x, w, axis="accl"),
            (4 * 8, 4, 16), (2, 32, 8))
    with pytest.raises(ValueError, match="local experts"):
        run(lambda x, w: ca.alltoall_matmul_body(x, w, axis="accl"),
            (4 * 8, 4, 16), (3, 16, 8))
    with pytest.raises(ValueError, match="divisible"):
        run(lambda h, w: ca.matmul_alltoall_body(h, w, axis="accl"),
            (4 * 2, 4 * 3 + 1, 16), (2, 16, 8))


def test_a2a_device_api_entry_points(accl, rng):
    """device_api.alltoall_matmul / matmul_alltoall compose in a
    shard_map body (the in-kernel collective discipline) and match the
    host oracle on whatever rung this is."""
    from jax.sharding import PartitionSpec as P

    from accl_tpu import device_api as dapi
    from accl_tpu.parallel.primitives import AXIS, _smap

    comm = _comm(4)
    W, el, C, d, h = 4, 2, 8, 32, 16
    x = _ints(rng, (W, W * el, C, d), lo=-2, hi=3)
    w_in = _ints(rng, (W, el, d, h), lo=-2, hi=3)
    w_out = _ints(rng, (W, el, h, d), lo=-2, hi=3)

    def body(xs, wi, wo):
        a = dapi.alltoall_matmul(xs[0], wi[0])
        z = dapi.matmul_alltoall(a.astype(xs.dtype), wo[0])
        return z[None]

    out = np.asarray(_smap(comm, body, 3,
                           in_specs=(P(AXIS), P(AXIS), P(AXIS)))(
        _put(comm, x), _put(comm, w_in), _put(comm, w_out)))
    acts = _host_dispatch(x, w_in)
    back = _host_combine(acts.astype(np.float32), w_out)
    np.testing.assert_array_equal(out, back.astype(np.float32))


# ---------------------------------------------------------------------------
# trace-level coverage of the kernels (every rung: tracing a pallas_call
# runs the whole kernel Python abstractly)
# ---------------------------------------------------------------------------

def _trace_a2a(monkeypatch, fn, xshape, wshape, out_spec=None):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))
    return str(jax.make_jaxpr(shard_map(
        fn, mesh=mesh, in_specs=(P("accl"), P(None)),
        out_specs=out_spec or P("accl"), check_vma=False))(
        jnp.zeros(xshape, jnp.float32), jnp.zeros(wshape, jnp.float32)))


def test_a2a_traces_kernels(accl, monkeypatch):
    """Both directions trace the fused kernel with overlap engaged —
    full kernel-Python coverage of the flat-exchange schedule on every
    rung — and overlap=False pins the unfused pair."""
    el, C, d, h = 2, 16, 32, 64
    t = _trace_a2a(monkeypatch,
                   lambda xs, ws: ca.alltoall_matmul_body(
                       xs, ws, axis="accl", overlap=True),
                   (4 * 4 * el, C, d), (el, d, h))
    assert t.count("pallas_call") == 1
    t = _trace_a2a(monkeypatch,
                   lambda hs, ws: ca.matmul_alltoall_body(
                       hs, ws, axis="accl", overlap=True),
                   (4 * el, 4 * C, h), (el, h, d))
    assert t.count("pallas_call") == 1
    t = _trace_a2a(monkeypatch,
                   lambda xs, ws: ca.alltoall_matmul_body(
                       xs, ws, axis="accl", overlap=False),
                   (4 * 4 * el, C, d), (el, d, h))
    assert "pallas_call" not in t
    # oversized: overlap requested but the plan misses the budget
    t = _trace_a2a(monkeypatch,
                   lambda xs, ws: ca.alltoall_matmul_body(
                       xs, ws, axis="accl", overlap=True),
                   (4 * 4 * 8, 1024, 4096), (8, 4096, 4096))
    assert "pallas_call" not in t


def test_a2a_wire_traces_cast_and_kernel(accl, monkeypatch):
    """bf16 wire staging traces the hp_compression cast lane plus the
    exchange kernel for dispatch (the payload is staged compressed),
    and the in-kernel staging only for combine (the y blocks compress
    inside the kernel — no separate cast). The bf16_sr codec threads
    through the same path; off-TPU the SR lane degrades to a plain
    ``astype`` (the TPU PRNG is unavailable), so its cast traces no
    kernel there while the exchange kernel still engages."""
    el, C, d, h = 2, 16, 128, 128
    on_tpu = jax.default_backend() == "tpu"
    for wire, casts in (("bf16", 1), ("bf16_sr", 1 if on_tpu else 0)):
        t = _trace_a2a(monkeypatch,
                       lambda xs, ws, wire=wire: ca.alltoall_matmul_body(
                           xs, ws, axis="accl", overlap=True,
                           wire_dtype=wire),
                       (4 * 4 * el, C, d), (el, d, h))
        assert t.count("pallas_call") == 1 + casts  # cast + exchange
    t = _trace_a2a(monkeypatch,
                   lambda hs, ws: ca.matmul_alltoall_body(
                       hs, ws, axis="accl", overlap=True,
                       wire_dtype="bf16"),
                   (4 * el, 4 * C, h), (el, h, d))
    assert t.count("pallas_call") == 1       # in-kernel staging only


def test_a2a_vjp_traces_fused_dual(accl, monkeypatch):
    """Both custom VJPs trace THREE fused kernels — the forward, the
    dual dx kernel (dispatch's dx is the combine kernel and vice
    versa), and the fused a2a-wgrad dw kernel (the gradient exchange
    folded into the per-expert contraction sweep). No unfused
    ``all_to_all`` survives in the backward."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))
    el, C, d, h = 2, 16, 32, 64

    def grad_trace(entry, xshape, wshape):
        def body(xs, ws):
            def loss(w_):
                return jnp.sum(entry(xs, w_, "accl", None, True))
            return jax.grad(loss)(ws)

        return str(jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P(None), check_vma=False))(
            jnp.zeros(xshape, jnp.float32), jnp.zeros(wshape, jnp.float32)))

    t = grad_trace(ca.alltoall_matmul, (4 * 4 * el, C, d), (el, d, h))
    assert t.count("pallas_call") == 3
    assert "all_to_all" not in t
    t = grad_trace(ca.matmul_alltoall, (4 * el, 4 * C, h), (el, h, d))
    assert t.count("pallas_call") == 3
    assert "all_to_all" not in t


# ---------------------------------------------------------------------------
# fallback telemetry: the a2a ops ride the shared counter
# ---------------------------------------------------------------------------

def test_a2a_fallback_counter_reasons(accl, monkeypatch):
    """accl_cmatmul_fallback_total generalizes to the a2a ops: every
    fused-path fallback counted by reason, the warn-once set dedupes
    only the log, an explicit overlap=False is never counted."""
    from jax import shard_map
    from accl_tpu.obs import metrics as obs_metrics
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    def trace(overlap, kavail, shape=(2, 16, 32, 64)):
        monkeypatch.setattr(cm, "_kernels_available", lambda: kavail)
        el, C, d, h = shape

        def body(xs, ws):
            return ca.alltoall_matmul_body(xs, ws, axis="accl",
                                           overlap=overlap)

        jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P("accl"), check_vma=False))(
            jnp.zeros((4 * 4 * el, C, d), jnp.float32),
            jnp.zeros((el, d, h), jnp.float32))

    def delta(fn):
        before = obs_metrics.snapshot()
        fn()
        d = obs_metrics.delta(before)["counters"]
        return {key: v for key, v in d.items()
                if key.startswith("accl_cmatmul_fallback_total")}

    key = ('accl_cmatmul_fallback_total{op="alltoall_matmul",'
           'reason="%s"}')
    d = delta(lambda: trace(True, False))
    assert d.get(key % "no_interpret") == 1
    saved_th = ca.get_overlap_threshold()
    try:
        ca.set_overlap_threshold(1 << 62)
        d = delta(lambda: trace(None, True))
        assert d.get(key % "threshold") == 1
    finally:
        ca.set_overlap_threshold(saved_th)
    d = delta(lambda: trace(True, True, shape=(8, 1024, 4096, 4096)))
    assert d.get(key % "vmem_miss") == 1
    # an explicit overlap=False is a REQUEST, not a fallback
    d = delta(lambda: trace(False, True))
    assert d == {}
    # ... and session-wide (moe_overlap=False)
    saved_ov = ca.get_overlap_enabled()
    try:
        ca.set_overlap_enabled(False)
        d = delta(lambda: trace(None, True))
        assert d == {}
    finally:
        ca.set_overlap_enabled(saved_ov)
    # the counter never dedupes
    d = delta(lambda: (trace(True, False), trace(True, False)))
    assert d.get(key % "no_interpret") == 2


def test_moe_engage_honesty(accl, rng, monkeypatch):
    """models/moe.py commits to the fused datapath only when BOTH
    direction kernels engage; a declined commit runs the lax baseline
    UNCHANGED (identical program) and counts once under the
    moe_alltoall label."""
    from accl_tpu.models import moe
    from accl_tpu.obs import metrics as obs_metrics

    comm = _comm(4)
    W, n, d, E, C = 4, 8, 16, 8, 4
    gp = moe.init_params(jax.random.PRNGKey(0), comm, d, 32, E)
    params = moe.shard_params(gp, comm)
    x = rng.standard_normal((W, n, d)).astype(np.float32)
    xg = _put(comm, x)
    base = np.asarray(
        moe.build_moe_forward(comm, E, C, overlap=False)(params, xg))

    # kernels unavailable: overlap=True COMMITS to the baseline (never a
    # degraded unfused rendition of the fused datapath) and counts
    monkeypatch.setattr(cm, "_kernels_available", lambda: False)
    before = obs_metrics.snapshot()
    got = np.asarray(
        moe.build_moe_forward(comm, E, C, overlap=True)(params, xg))
    np.testing.assert_array_equal(got, base)
    delta = obs_metrics.delta(before)["counters"]
    key = ('accl_cmatmul_fallback_total{op="moe_alltoall",'
           'reason="no_interpret"}')
    assert delta.get(key) == 1
    # session register declines at overlap=None -> threshold reason
    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    saved_th = ca.get_overlap_threshold()
    try:
        ca.set_overlap_threshold(1 << 62)
        before = obs_metrics.snapshot()
        got = np.asarray(
            moe.build_moe_forward(comm, E, C)(params, xg))
        np.testing.assert_allclose(got, base, rtol=1e-6, atol=1e-7)
        delta = obs_metrics.delta(before)["counters"]
        key = ('accl_cmatmul_fallback_total{op="moe_alltoall",'
               'reason="threshold"}')
        assert delta.get(key) == 1
    finally:
        ca.set_overlap_threshold(saved_th)
    # an explicit overlap=False never counts
    before = obs_metrics.snapshot()
    moe.build_moe_forward(comm, E, C, overlap=False)(params, xg)
    delta = obs_metrics.delta(before)["counters"]
    assert not any(k.startswith('accl_cmatmul_fallback_total'
                                '{op="moe_alltoall"')
                   for k in delta)


# ---------------------------------------------------------------------------
# the flagship workload: moe loss trajectories, overlap on vs off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W", [4, 8])
def test_moe_loss_trajectory_overlap_ab(accl, rng, W):
    """Training through build_moe_forward produces identical loss
    trajectories (fp tolerance) with the fused a2a datapath on vs off —
    selectable per call. On rungs where the kernels cannot run both
    paths resolve to the identical baseline program."""
    from accl_tpu.models import moe

    comm = _comm(W)
    n, d, h, E, C = 8, 16, 32, 2 * W, 8
    gp = moe.init_params(jax.random.PRNGKey(1), comm, d, h, E)
    x = rng.standard_normal((W, n, d)).astype(np.float32)
    t = rng.standard_normal((W, n, d)).astype(np.float32)
    xg, tg = _put(comm, x), _put(comm, t)
    traj = {}
    for ov in (False, True):
        params = moe.shard_params(gp, comm)
        fwd = moe.build_moe_forward(comm, E, C, overlap=ov)

        def loss_fn(p):
            return jnp.mean((fwd(p, xg) - tg) ** 2)

        traj[ov] = []
        for _ in range(3):
            loss, g = jax.value_and_grad(loss_fn)(params)
            params = jax.tree_util.tree_map(
                lambda w_, g_: w_ - 5e-2 * g_, params, g)
            traj[ov].append(float(loss))
    np.testing.assert_allclose(traj[True], traj[False],
                               rtol=1e-5, atol=1e-7)
    assert traj[True][-1] < traj[True][0]   # it actually trains


# ---------------------------------------------------------------------------
# round 20: the fused a2a-wgrad (dw) leg — parity on every rung, plan pins
# ---------------------------------------------------------------------------

def test_a2a_wgrad_body_both_orientations(accl, rng):
    """a2a_gathered_wgrad_body vs host math on every rung: dispatch's
    dw contracts the exchanged tokens against the local dy (travel_lhs)
    and combine's dw contracts the local h against the exchanged dy —
    the kernel-less rung runs the unfused ``all_to_all`` + einsum
    fallback, same math by construction, so this pins BOTH datapaths to
    the same integers."""
    from jax.sharding import PartitionSpec as P

    from accl_tpu.parallel.primitives import AXIS, _smap

    comm = _comm(4)
    W, el, C, ct, cl = 4, 2, 8, 32, 16
    trav = _ints(rng, (W, W * el, C, ct), lo=-3, hi=4)
    loc = _ints(rng, (W, el, W * C, cl), lo=-3, hi=4)

    def run(travel_lhs):
        def body(ts, ls):
            return ca.a2a_gathered_wgrad_body(
                ts[0], ls[0], axis=AXIS, travel_lhs=travel_lhs)[None]

        return np.asarray(_smap(comm, body, 2,
                                in_specs=(P(AXIS), P(AXIS)))(
            _put(comm, trav), _put(comm, loc)))

    for lhs in (True, False):
        got = run(lhs)
        for r in range(W):
            for e in range(el):
                recv = np.concatenate(
                    [trav[p, r * el + e] for p in range(W)],
                    axis=0).astype(np.float64)          # (W*C, ct)
                lo_ = loc[r, e].astype(np.float64)      # (W*C, cl)
                want = recv.T @ lo_ if lhs else lo_.T @ recv
                np.testing.assert_array_equal(
                    got[r, e], want.astype(np.float32))


def test_a2a_wgrad_plan_pins():
    """The fused a2a-wgrad geometry contract: capacity rows padded by
    the stricter sublane, lane-padded panels, the f32 (ct, cl) dw
    accumulators resident — None beyond the budget (the VJP keeps the
    unfused dw pair there, counted under ``moe_a2a_dw``)."""
    p = ca.a2a_wgrad_plan(2, 8, 32, 64, 4, jnp.float32, True)
    assert p is not None and p["mode"] == "resident"
    assert (p["cp"], p["ctp"], p["clp"], p["nchan"]) == (8, 128, 128, 2)
    assert p["vmem_bytes"] <= cm._VMEM_BUDGET
    # unidirectional / small world: one channel
    p = ca.a2a_wgrad_plan(2, 8, 32, 64, 2, jnp.float32, True)
    assert p is not None and p["nchan"] == 1
    # a dw panel set beyond the budget declines honestly
    assert ca.a2a_wgrad_plan(64, 512, 4096, 4096, 8, jnp.float32,
                             True) is None
    # engage vocabulary: "off" when the session dw register is down
    saved = ca.get_dw_overlap_enabled()
    try:
        ca.set_dw_overlap_enabled(False)
        assert ca.a2a_wgrad_engage_reason(
            2, 8, 32, 64, 4, jnp.float32, overlap=True) == "off"
    finally:
        ca.set_dw_overlap_enabled(saved)


def test_a2a_dw_config_write_through(accl):
    """ACCLConfig.moe_dw_overlap lands in the a2a module at every
    config assignment (the cmatmul_overlap write-through shape)."""
    saved = accl.config
    try:
        accl.config = accl.config.replace(moe_dw_overlap=False)
        assert ca.get_dw_overlap_enabled() is False
        accl.config = accl.config.replace(moe_dw_overlap=True)
        assert ca.get_dw_overlap_enabled() is True
    finally:
        accl.config = saved
