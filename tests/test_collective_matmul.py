"""Collective matmul (ops/collective_matmul.py): comm/compute-overlapped
all-gather x matmul and matmul x reduce-scatter.

Parity is BIT-exact fp32 against the unfused XLA pair: operands are
integer-valued floats (every product and partial sum is exactly
representable), so any reassociation the ring schedule introduces cannot
hide behind tolerance. Kernel suites run under the TPU interpreter's
simulated remote DMA; the policy/fallback/model tests check that the
overlapped entry points resolve to the unfused pair where a plan
declines, same math by construction.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accl_tpu import Algorithm
from accl_tpu.communicator import Communicator
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


def _run_agmm(comm, x, w, algo, bidirectional):
    prog = algorithms.build_allgather_matmul(
        comm, algo, bidirectional=bidirectional)
    return np.asarray(prog(_put(comm, x), _put(comm, w)))


def _run_mmrs(comm, x, w, algo, bidirectional):
    prog = algorithms.build_matmul_reduce_scatter(
        comm, algo, bidirectional=bidirectional)
    return np.asarray(prog(_put(comm, x), _put(comm, w)))


# ---------------------------------------------------------------------------
# interpreter parity: fused kernels vs the unfused XLA pair, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W", [2, 4, 8])
@pytest.mark.parametrize("shape", [(16, 128, 128),    # dense, tile-aligned
                                   (12, 72, 40)])     # uneven-divisible
def test_agmm_parity_bit_exact(accl, rng, W, shape):
    m, k, n = shape
    x = _ints(rng, (W, m, k))
    w = _ints(rng, (W, k, n))
    comm = _comm(W)
    fused = _run_agmm(comm, x, w, Algorithm.PALLAS, bidirectional=False)
    ref = _run_agmm(comm, x, w, Algorithm.XLA, bidirectional=False)
    np.testing.assert_array_equal(fused, ref)
    # and vs host math: rank r's output is all rows times ITS w block
    gathered = x.reshape(W * m, k)
    for r in range(W):
        np.testing.assert_array_equal(fused[r], gathered @ w[r])


@pytest.mark.parametrize("W", [4, 8])
@pytest.mark.parametrize("shape", [(16, 128, 128), (12, 72, 40)])
def test_agmm_parity_bidirectional(accl, rng, W, shape):
    """The counter-rotating row-half channels (P >= 4) are output-
    identical to the unidirectional ring and the XLA pair."""
    m, k, n = shape
    x = _ints(rng, (W, m, k))
    w = _ints(rng, (W, k, n))
    comm = _comm(W)
    fused = _run_agmm(comm, x, w, Algorithm.PALLAS, bidirectional=True)
    ref = _run_agmm(comm, x, w, Algorithm.XLA, bidirectional=True)
    np.testing.assert_array_equal(fused, ref)


@pytest.mark.parametrize("W", [2, 4, 8])
@pytest.mark.parametrize("shape", [(16, 128, 128), (12, 72, 40)])
def test_mmrs_parity_bit_exact(accl, rng, W, shape):
    m, k, n = shape
    x = _ints(rng, (W, W * m, k), lo=-3, hi=4)
    w = _ints(rng, (W, k, n), lo=-3, hi=4)
    comm = _comm(W)
    fused = _run_mmrs(comm, x, w, Algorithm.PALLAS, bidirectional=False)
    # integer-valued operands: the ring's fold order and psum's order
    # agree exactly
    ref = _run_mmrs(comm, x, w, Algorithm.XLA, bidirectional=False)
    np.testing.assert_array_equal(fused, ref)
    host = np.einsum("rmk,rkn->rmn", x.astype(np.float64),
                     w.astype(np.float64)).sum(0)
    for r in range(W):
        np.testing.assert_array_equal(
            fused[r], host[r * m:(r + 1) * m].astype(np.float32))


@pytest.mark.parametrize("W", [4, 8])
@pytest.mark.parametrize("shape", [(16, 128, 128), (12, 72, 40)])
def test_mmrs_parity_bidirectional(accl, rng, W, shape):
    m, k, n = shape
    x = _ints(rng, (W, W * m, k), lo=-3, hi=4)
    w = _ints(rng, (W, k, n), lo=-3, hi=4)
    comm = _comm(W)
    fused = _run_mmrs(comm, x, w, Algorithm.PALLAS, bidirectional=True)
    ref = _run_mmrs(comm, x, w, Algorithm.XLA, bidirectional=True)
    np.testing.assert_array_equal(fused, ref)


def test_cmatmul_race_free(accl, rng, monkeypatch):
    """Both ring kernels, uni- and bidirectional, under the interpret-mode
    race detector: the double-buffer credit protocol (grants == gates)
    must hold with the MXU folded into the schedule."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(
        pallas_ring, "_interpret_params",
        lambda: pltpu.InterpretParams(detect_races=True))
    comm = _comm(WORLD)
    m, k, n = 16, 128, 128
    x_ag = _ints(rng, (WORLD, m, k))
    x_rs = _ints(rng, (WORLD, WORLD * m, k), lo=-3, hi=4)
    w = _ints(rng, (WORLD, k, n), lo=-3, hi=4)
    for bidir in (False, True):
        fused = _run_agmm(comm, x_ag, w, Algorithm.PALLAS, bidir)
        ref = _run_agmm(comm, x_ag, w, Algorithm.XLA, bidir)
        np.testing.assert_array_equal(fused, ref)
        fused = _run_mmrs(comm, x_rs, w, Algorithm.PALLAS, bidir)
        ref = _run_mmrs(comm, x_rs, w, Algorithm.XLA, bidir)
        np.testing.assert_array_equal(fused, ref)


def test_cmatmul_grads_through_kernels(accl, rng):
    """The custom VJPs (each kernel's backward is the other kernel) match
    the grads of the unfused pair — same integer-exactness trick."""
    from accl_tpu.parallel.primitives import AXIS, _smap

    comm = _comm(4)
    W, m, k, n = 4, 8, 64, 32
    x = _ints(rng, (W, m, k), lo=-2, hi=3)
    w = _ints(rng, (W, k, n), lo=-2, hi=3)

    def make(overlap):
        def body(xs, ws):
            def loss(ws_):
                y = cm.all_gather_matmul(xs[0], ws_, AXIS, None, overlap)
                z = cm.matmul_reduce_scatter(
                    y.astype(xs.dtype), jnp.transpose(ws_), AXIS, None,
                    overlap)
                return jnp.sum(z)

            return jax.grad(loss)(ws[0])[None]

        return _smap(comm, body, 2)

    g_fused = np.asarray(make(True)(_put(comm, x), _put(comm, w)))
    g_ref = np.asarray(make(False)(_put(comm, x), _put(comm, w)))
    np.testing.assert_array_equal(g_fused, g_ref)


# ---------------------------------------------------------------------------
# block-geometry policy (every rung)
# ---------------------------------------------------------------------------

def test_plan_geometry_pins():
    """The plan is the kernel's geometry contract — pin it so a silent
    padding change shows up as a diff, not a VMEM surprise on silicon."""
    p = cm.agmm_plan(12, 72, 40, 4, jnp.float32, bidirectional=False)
    assert (p["mp"], p["kp"], p["np"], p["nchan"]) == (16, 128, 128, 1)
    p = cm.agmm_plan(12, 72, 40, 4, jnp.float32, bidirectional=True)
    assert (p["mp"], p["nchan"]) == (16, 2)  # rows pad to 2x sublane
    p = cm.mmrs_plan(48, 72, 40, 4, jnp.float32, bidirectional=True)
    assert (p["cp"], p["kp"], p["np"], p["nchan"]) == (16, 128, 128, 2)
    # bf16 staging: 16-row sublane tiles
    p = cm.agmm_plan(8, 128, 128, 4, jnp.bfloat16, bidirectional=False)
    assert p["mp"] == 16


def test_plan_vmem_budget_fallback():
    """Geometry that misses the scoped-VMEM budget — in EVERY arm,
    resident and n-blocked streaming — returns None, the unfused-XLA
    fallback trigger. The irreducible term is the lane-aligned weight
    panel: at (8, 128, 32768) one (kp, nb) f32 column block alone
    exceeds the budget, so no amount of accumulator blocking saves
    it."""
    assert cm.agmm_plan(8, 128, 32768, 8, jnp.float32, False) is None
    assert cm.mmrs_plan(8 * 4096, 4096, 4096, 8, jnp.float32, False) is None
    # m not divisible by world is never a kernel plan
    assert cm.mmrs_plan(13, 64, 64, 4, jnp.float32, False) is None
    ok = cm.agmm_plan(64, 256, 256, 8, jnp.float32, False)
    assert ok is not None and ok["vmem_bytes"] <= cm._VMEM_BUDGET


def test_overlap_off_never_traces_kernels(accl, monkeypatch):
    """overlap=False (per call) and oversized plans pin the unfused XLA
    pair — no pallas_call may appear in the traced program. (Kernel
    availability is forced so the assertion bites on every rung.)"""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    def trace(m, k, n, overlap):
        def body(xs, ws):
            return cm.all_gather_matmul_body(xs, ws, axis="accl",
                                             overlap=overlap)

        return str(jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P("accl"), check_vma=False))(
            jnp.zeros((4 * m, k), jnp.float32),
            jnp.zeros((k, n), jnp.float32)))

    assert "pallas_call" not in trace(16, 64, 64, overlap=False)
    # oversized: overlap requested but the plan misses the budget in
    # every arm (the irreducible weight-panel shape — 4096³ now rides
    # the n-blocked streaming plan instead of declining)
    assert "pallas_call" not in trace(8, 128, 32768, overlap=True)


def test_session_config_write_through(accl):
    """ACCLConfig.cmatmul_overlap lands in the kernel module on every
    config assignment (the flash_bwd write-through discipline)."""
    saved = accl.config
    try:
        accl.config = accl.config.replace(cmatmul_overlap=False)
        assert cm.get_overlap_enabled() is False
        accl.config = accl.config.replace(cmatmul_overlap=True)
        assert cm.get_overlap_enabled() is True
    finally:
        accl.config = saved


def test_body_rejects_bad_shapes(accl):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    def run(body, xshape, wshape):
        f = shard_map(body, mesh=mesh, in_specs=(P("accl"), P(None)),
                      out_specs=P("accl"), check_vma=False)
        return jax.make_jaxpr(f)(jnp.zeros(xshape, jnp.float32),
                                 jnp.zeros(wshape, jnp.float32))

    with pytest.raises(ValueError, match="contraction"):
        run(lambda x, w: cm.all_gather_matmul_body(x, w, axis="accl"),
            (4 * 8, 16), (32, 8))
    with pytest.raises(ValueError, match="divisible"):
        run(lambda x, w: cm.matmul_reduce_scatter_body(x, w, axis="accl"),
            (4 * 13, 16), (16, 8))


# ---------------------------------------------------------------------------
# the duals agree on every rung (XLA fallback path): structure A/B
# ---------------------------------------------------------------------------

def test_fallback_grads_match_plain_math(accl, rng):
    """grad through the custom VJPs == grad of the plain gathered math,
    on whatever rung this is (kernels or fallback)."""
    from accl_tpu.parallel.primitives import AXIS, _smap
    from jax import lax

    comm = _comm(4)
    W, m, k, n = 4, 8, 32, 16
    x = _ints(rng, (W, m, k), lo=-2, hi=3)
    w = _ints(rng, (W, k, n), lo=-2, hi=3)

    def body_vjp(xs, ws):
        def loss(ws_):
            return jnp.sum(cm.all_gather_matmul(xs[0], ws_, AXIS))

        return jax.grad(loss)(ws[0])[None]

    def body_plain(xs, ws):
        def loss(ws_):
            xg = lax.all_gather(xs[0], AXIS, axis=0, tiled=True)
            return jnp.sum(jnp.dot(xg, ws_,
                                   preferred_element_type=jnp.float32))

        return jax.grad(loss)(ws[0])[None]

    g1 = np.asarray(_smap(comm, body_vjp, 2)(_put(comm, x), _put(comm, w)))
    g2 = np.asarray(_smap(comm, body_plain, 2)(_put(comm, x), _put(comm, w)))
    np.testing.assert_array_equal(g1, g2)


# ---------------------------------------------------------------------------
# the flagship workload: mlp loss trajectories, overlap on vs off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dp,tp", [(1, 4), (2, 4)])
def test_mlp_loss_trajectory_overlap_ab(rng, dp, tp):
    """The train step produces identical loss trajectories (fp tolerance)
    with the overlapped TP datapath on vs off — selectable per call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accl_tpu.models import mlp

    d, h, b = 16, 64, 8
    mesh = mlp.make_mesh(jax.devices()[: dp * tp], dp=dp, tp=tp)
    params = mlp.shard_params(
        mlp.init_params(jax.random.PRNGKey(1), d, h), mesh)
    sh = NamedSharding(mesh, P(mlp.DP_AXIS, None))
    x = jax.device_put(
        rng.standard_normal((dp * b, d)).astype(np.float32), sh)
    t = jax.device_put(
        rng.standard_normal((dp * b, d)).astype(np.float32), sh)
    traj = {}
    for ov in (False, True):
        p = params
        step = mlp.make_train_step(mesh, lr=5e-2, overlap=ov)
        traj[ov] = []
        for _ in range(4):
            p, loss = step(p, x, t)
            traj[ov].append(float(loss))
    np.testing.assert_allclose(traj[True], traj[False],
                               rtol=1e-5, atol=1e-7)
    assert traj[True][-1] < traj[True][0]  # it actually trains


def test_mlp_session_selectable(rng):
    """overlap=None follows ACCLConfig.cmatmul_overlap (via the
    kernel-module engage checks) at build time; the session switch off
    disengages both stages regardless of shapes."""
    from accl_tpu.models import mlp

    mesh = mlp.make_mesh(jax.devices()[:4], dp=1, tp=4)
    saved = cm.get_overlap_enabled()
    saved_th = cm.get_overlap_thresholds()
    try:
        cm.set_overlap_thresholds(0, 0)
        cm.set_overlap_enabled(False)
        assert cm.agmm_engages(8, 32, 32, 4, jnp.float32, None) is False
        cm.set_overlap_enabled(True)
        assert cm.agmm_engages(8, 32, 32, 4, jnp.float32, None) \
            == cm._kernels_available()
        assert cm.agmm_engages(8, 32, 32, 4, jnp.float32, False) is False
    finally:
        cm.set_overlap_enabled(saved)
        cm.set_overlap_thresholds(*saved_th)
    # and make_forward under each mode still computes the same values
    d, h, b = 8, 32, 8
    params = mlp.shard_params(
        mlp.init_params(jax.random.PRNGKey(0), d, h), mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.device_put(rng.standard_normal((b, d)).astype(np.float32),
                       NamedSharding(mesh, P(mlp.DP_AXIS, None)))
    y0 = np.asarray(mlp.make_forward(mesh, overlap=False)(params, x))
    y1 = np.asarray(mlp.make_forward(mesh, overlap=True)(params, x))
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# dispatch plumbing
# ---------------------------------------------------------------------------

def test_select_new_operations(accl):
    """Dispatch plumbing for the overlap ops (the exact threshold-edge
    bytes are pinned in test_algorithms.py with the other registers):
    off-ICI never auto-selects the kernels, explicit requests win, and
    unsupported families are rejected."""
    from accl_tpu.config import TransportBackend
    from accl_tpu.constants import operation

    comm = accl.global_comm()
    ici = accl.config.replace(transport=TransportBackend.ICI)
    for op, th in ((operation.allgather_matmul, ici.ag_matmul_threshold),
                   (operation.matmul_reduce_scatter,
                    ici.rs_matmul_threshold)):
        assert algorithms.select(op, th, comm, accl.config) == Algorithm.XLA
        # explicit request wins; unsupported families are rejected
        assert algorithms.select(op, 0, comm, ici,
                                 Algorithm.PALLAS) == Algorithm.PALLAS
        with pytest.raises(ValueError):
            algorithms.select(op, th, comm, ici, Algorithm.RING)


def test_threshold_write_through_gates_session_default(accl, monkeypatch):
    """The tuned size registers reach the DEVICE-API path: at
    overlap=None the kernel module's write-through thresholds decide
    fused-vs-XLA (DISABLED pins the pair), while an explicit
    overlap=True bypasses them (the per-call force)."""
    from accl_tpu.bench.autotune import DISABLED
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))
    m, k, n = 16, 64, 64

    def trace(overlap):
        def body(xs, ws):
            return cm.all_gather_matmul_body(xs, ws, axis="accl",
                                             overlap=overlap)

        return str(jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P("accl"), check_vma=False))(
            jnp.zeros((4 * m, k), jnp.float32),
            jnp.zeros((k, n), jnp.float32)))

    saved = accl.config
    try:
        shard_bytes = m * k * 4
        # register above the payload -> session default resolves to XLA
        accl.config = accl.config.replace(
            ag_matmul_threshold=shard_bytes + 1)
        assert cm.get_overlap_thresholds()[0] == shard_bytes + 1
        assert "pallas_call" not in trace(overlap=None)
        assert "pallas_call" in trace(overlap=True)   # per-call force
        # at/below the payload -> fused engages by default
        accl.config = accl.config.replace(ag_matmul_threshold=shard_bytes)
        assert "pallas_call" in trace(overlap=None)
        # the autotune DISABLED sentinel turns overlap off by default
        accl.config = accl.config.replace(ag_matmul_threshold=DISABLED)
        assert "pallas_call" not in trace(overlap=None)
    finally:
        accl.config = saved


def test_device_api_entry_points(accl, rng):
    """device_api.all_gather_matmul / matmul_reduce_scatter compose in a
    shard_map body (the in-kernel collective discipline)."""
    from accl_tpu import device_api as dapi
    from accl_tpu.parallel.primitives import _smap

    comm = _comm(4)
    W, m, k, n = 4, 8, 32, 16
    x = _ints(rng, (W, m, k))
    w = _ints(rng, (W, k, n))

    def body(xs, ws):
        y = dapi.all_gather_matmul(xs[0], ws[0])
        z = dapi.matmul_reduce_scatter(y.astype(xs.dtype),
                                       jnp.transpose(ws[0]))
        return z[None]

    out = np.asarray(_smap(comm, body, 2)(_put(comm, x), _put(comm, w)))
    xg = x.reshape(W * m, k).astype(np.float64)
    full = np.stack([xg @ w[r] for r in range(W)])          # (W, W*m, n)
    z_full = (full @ np.transpose(w, (0, 2, 1)).astype(np.float64)).sum(0)
    for r in range(W):
        np.testing.assert_array_equal(
            out[r], z_full[r * m:(r + 1) * m].astype(np.float32))


# ---------------------------------------------------------------------------
# round 9: k-blocked streaming plans (every rung)
# ---------------------------------------------------------------------------

def test_plan_streaming_engages():
    """Shapes whose FULL staged shard misses the 12 MiB budget no longer
    return None — the plan picks a lane-aligned k-block and streams
    (the acceptance shape class that previously fell back to XLA)."""
    # resident keeps its mode + degenerate k-block fields
    p = cm.agmm_plan(16, 128, 128, 4, jnp.float32, False)
    assert p["mode"] == "resident" and (p["kb"], p["nkb"]) == (128, 1)
    # big-k: the (kp, n) weight block alone busts the resident budget
    p = cm.agmm_plan(256, 8192, 512, 8, jnp.float32, False)
    assert p is not None and p["mode"] == "stream"
    assert p["kb"] % 128 == 0 and p["nkb"] == -(-p["kp"] // p["kb"])
    assert p["vmem_bytes"] <= cm._VMEM_BUDGET
    assert p["kb"] * p["nkb"] == p["kp"] and p["kp"] >= 8192
    p = cm.mmrs_plan(8 * 256, 8192, 512, 8, jnp.float32, False)
    assert p is not None and p["mode"] == "stream"
    assert p["vmem_bytes"] <= cm._VMEM_BUDGET
    # bidirectional streaming keeps the channel split
    p = cm.agmm_plan(256, 8192, 512, 8, jnp.float32, True)
    assert p["mode"] == "stream" and p["nchan"] == 2
    # the m x n accumulator floor is no longer irreducible: the
    # n-blocked streaming arm (round 20) splits the accumulator's lane
    # columns and 4096³ resolves to a stream plan with both blockings
    p = cm.agmm_plan(4096, 4096, 4096, 8, jnp.float32, False)
    assert p is not None and p["mode"] == "stream"
    assert (p["mb"], p["nmb"], p["kb"], p["nkb"]) == (256, 16, 128, 32)
    assert p["vmem_bytes"] <= cm._VMEM_BUDGET
    # the lane-aligned weight panel IS irreducible: one (kp, nb) f32
    # column block alone busts the budget — still an honest decline
    assert cm.agmm_plan(8, 128, 32768, 8, jnp.float32, False) is None


def test_plan_wire_sizing():
    """A wire dtype halves the staged/transferred terms: a shape whose
    f32 plan streams can become resident under bf16 staging, and the
    row padding follows the WIRE dtype's sublane tiles."""
    # resident: the staged x terms halve
    full = cm.agmm_plan(64, 1024, 256, 4, jnp.float32, False)
    half = cm.agmm_plan(64, 1024, 256, 4, jnp.float32, False,
                        wire_dtype=jnp.bfloat16)
    assert full["mode"] == half["mode"] == "resident"
    assert half["vmem_bytes"] < full["vmem_bytes"]
    # streaming: cheaper per-block staging affords a k-block at least
    # as large (fewer segments for the same budget)
    full = cm.agmm_plan(256, 4096, 512, 8, jnp.float32, False)
    half = cm.agmm_plan(256, 4096, 512, 8, jnp.float32, False,
                        wire_dtype=jnp.bfloat16)
    assert full["mode"] == half["mode"] == "stream"
    assert half["kb"] >= full["kb"]
    # bf16 staging pads rows to 16-row sublane tiles
    p = cm.agmm_plan(8, 128, 128, 4, jnp.float32, False,
                     wire_dtype=jnp.bfloat16)
    assert p["mp"] == 16
    # mmrs: the travelling accumulator's wire terms shrink
    full = cm.mmrs_plan(8 * 64, 512, 2048, 8, jnp.float32, False)
    half = cm.mmrs_plan(8 * 64, 512, 2048, 8, jnp.float32, False,
                        wire_dtype=jnp.bfloat16)
    assert half["vmem_bytes"] < full["vmem_bytes"]


def test_wgrad_plan_pins():
    """The fused-wgrad geometry contract: padded rows by the stricter
    sublane, lane-padded panels, VMEM under budget — and None beyond
    (the VJP keeps the unfused gathered dw there)."""
    p = cm.wgrad_plan(256, 512, 512, 8, jnp.float32, jnp.float32, True)
    assert (p["msp"], p["ctp"], p["clp"], p["nchan"]) == (256, 512, 512, 2)
    assert p["vmem_bytes"] <= cm._VMEM_BUDGET
    # bf16 travelling shard: 16-row sublane padding
    p = cm.wgrad_plan(8, 64, 64, 4, jnp.bfloat16, jnp.float32, False)
    assert p["msp"] == 16
    # a dw panel beyond the budget falls back
    assert cm.wgrad_plan(256, 8192, 8192, 8, jnp.float32, jnp.float32,
                         True) is None
    assert cm.wgrad_plan(0, 64, 64, 4, jnp.float32, jnp.float32,
                         False) is None


# ---------------------------------------------------------------------------
# aspect-class thresholds + wire registers (every rung)
# ---------------------------------------------------------------------------

def test_aspect_class_thresholds(accl):
    """Per-class registers override the scalar for their class only and
    write through from the config like every other cmatmul knob."""
    assert cm.aspect_class(512, 512) == "square"
    assert cm.aspect_class(256, 1024) == "wide"
    assert cm.aspect_class(1024, 256) == "tall"
    saved = accl.config
    saved_cls = cm.get_overlap_class_thresholds()
    try:
        accl.config = accl.config.replace(
            ag_matmul_class_thresholds={"wide": 64},
            rs_matmul_class_thresholds={"tall": 128})
        assert cm.get_overlap_class_thresholds() == ({"wide": 64},
                                                     {"tall": 128})
        assert cm._ag_threshold(256, 1024) == 64          # class override
        assert cm._ag_threshold(512, 512) == \
            accl.config.ag_matmul_threshold                # scalar fallback
        assert cm._rs_threshold(1024, 256) == 128
    finally:
        accl.config = saved
        cm.set_overlap_class_thresholds(*saved_cls)


def test_wire_write_through_and_validation(accl):
    """ACCLConfig.cmatmul_wire_dtype lands in the kernel module on every
    config assignment; bad names fail loudly; per-call resolution never
    upcasts and honors the "off" override."""
    saved = accl.config
    try:
        accl.config = accl.config.replace(cmatmul_wire_dtype="bf16")
        assert cm.get_wire_dtype() == "bf16"
        # session default resolves; "off" forces full precision
        assert cm._resolve_wire(None, jnp.float32) == jnp.bfloat16
        assert cm._resolve_wire("off", jnp.float32) is None
        # never upcasts: bf16 operands have nothing to compress
        assert cm._resolve_wire(None, jnp.bfloat16) is None
        assert cm.wire_itemsize(jnp.float32) == 2          # session bf16
        assert cm.wire_itemsize(jnp.float32, "off") == 4
        accl.config = accl.config.replace(cmatmul_wire_dtype=None)
        assert cm.get_wire_dtype() is None
        assert cm.wire_itemsize(jnp.float32) == 4
        with pytest.raises(ValueError, match="wire dtype"):
            cm.set_wire_dtype("int3")
        # the per-call override validates too (a typo must name the
        # valid lanes, not die with a bare KeyError at trace time)
        with pytest.raises(ValueError, match="wire dtype"):
            cm._resolve_wire("fp16", jnp.float32)
    finally:
        accl.config = saved


def test_wire_effective_bytes_gate_engage(accl, monkeypatch):
    """The size registers see EFFECTIVE wire bytes: a shard exactly at
    the f32 threshold disengages under bf16 staging (it moves half the
    bytes, so it no longer clears the byte register)."""
    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    m, k, n = 16, 64, 64
    saved_th = cm.get_overlap_thresholds()
    saved_w = cm.get_wire_dtype()
    try:
        cm.set_overlap_thresholds(m * k * 4, 0)
        cm.set_wire_dtype(None)
        assert cm.agmm_engages(m, k, n, 4, jnp.float32, None) is True
        cm.set_wire_dtype("bf16")
        assert cm.agmm_engages(m, k, n, 4, jnp.float32, None) is False
        # the explicit per-call force still bypasses the register
        assert cm.agmm_engages(m, k, n, 4, jnp.float32, True) is True
    finally:
        cm.set_overlap_thresholds(*saved_th)
        cm.set_wire_dtype(saved_w)


def test_select_sees_effective_wire_bytes(accl):
    """parallel.algorithms.select scales the matmul ops' nbytes to wire
    bytes under the session wire dtype — the same payload that clears
    the register at f32 no longer clears it staged bf16."""
    from accl_tpu.config import TransportBackend
    from accl_tpu.constants import operation

    comm = accl.global_comm()
    ici = accl.config.replace(transport=TransportBackend.ICI)
    th = ici.ag_matmul_threshold
    assert algorithms.select(operation.allgather_matmul, th, comm,
                             ici) == Algorithm.PALLAS
    wired = ici.replace(cmatmul_wire_dtype="bf16")
    assert algorithms.select(operation.allgather_matmul, th, comm,
                             wired) == Algorithm.XLA
    # twice the payload clears it again (half the bytes on the wire)
    assert algorithms.select(operation.allgather_matmul, 2 * th, comm,
                             wired) == Algorithm.PALLAS
    # cmatmul_wire_bytes: count resolves the operand width exactly
    assert algorithms.cmatmul_wire_bytes(
        operation.allgather_matmul, 1024, wired) == 512
    assert algorithms.cmatmul_wire_bytes(
        operation.allgather_matmul, 1024, wired, count=512) == 1024


# ---------------------------------------------------------------------------
# trace-level coverage of the new kernels (every rung: tracing a
# pallas_call runs the whole kernel Python abstractly)
# ---------------------------------------------------------------------------

def _trace_body(monkeypatch, fn, xshape, wshape, out_spec=None):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))
    return str(jax.make_jaxpr(shard_map(
        fn, mesh=mesh, in_specs=(P("accl"), P(None)),
        out_specs=out_spec or P("accl"), check_vma=False))(
        jnp.zeros(xshape, jnp.float32), jnp.zeros(wshape, jnp.float32)))


def test_streaming_traces_kernels(accl, monkeypatch):
    """The streaming shapes now trace the fused kernel (before round 9
    they traced the unfused XLA pair): full kernel-Python coverage of
    the segment schedule on every rung."""
    m, k, n = 64, 8192, 256
    assert cm.agmm_plan(m, k, n, 4, jnp.float32, True)["mode"] == "stream"
    t = _trace_body(monkeypatch,
                    lambda xs, ws: cm.all_gather_matmul_body(
                        xs, ws, axis="accl", overlap=True),
                    (4 * m, k), (k, n))
    assert "pallas_call" in t
    assert cm.mmrs_plan(4 * m, k, n, 4, jnp.float32, True)["mode"] \
        == "stream"
    t = _trace_body(monkeypatch,
                    lambda xs, ws: cm.matmul_reduce_scatter_body(
                        xs, ws, axis="accl", overlap=True),
                    (4 * m, k), (k, n))
    assert "pallas_call" in t


def test_wire_traces_cast_and_kernel(accl, monkeypatch):
    """bf16 wire staging traces the hp_compression cast lane plus the
    ring kernel for agmm (the shard is staged compressed), and the
    in-kernel wire buffer for mmrs (the accumulator compresses inside
    the kernel — no separate cast)."""
    t = _trace_body(monkeypatch,
                    lambda xs, ws: cm.all_gather_matmul_body(
                        xs, ws, axis="accl", overlap=True,
                        wire_dtype="bf16"),
                    (4 * 16, 128, ), (128, 128))
    assert t.count("pallas_call") == 2      # pallas_cast + ring kernel
    t = _trace_body(monkeypatch,
                    lambda xs, ws: cm.matmul_reduce_scatter_body(
                        xs, ws, axis="accl", overlap=True,
                        wire_dtype="bf16"),
                    (4 * 16, 128), (128, 128))
    assert t.count("pallas_call") == 1      # in-kernel wire staging


def test_vjp_traces_fused_dw(accl, monkeypatch):
    """Both custom VJPs now trace THREE fused kernels: the forward, the
    dual dx kernel, and the fused gathered-wgrad dw kernel (dw was an
    unfused all_gather + matmul through round 8)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    def grad_trace(entry):
        def body(xs, ws):
            def loss(w_):
                return jnp.sum(entry(xs, w_, "accl", None, True))
            return jax.grad(loss)(ws)

        return str(jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P(None), check_vma=False))(
            jnp.zeros((4 * 16, 64), jnp.float32),
            jnp.zeros((64, 64), jnp.float32)))

    assert grad_trace(cm.all_gather_matmul).count("pallas_call") == 3
    assert grad_trace(cm.matmul_reduce_scatter).count("pallas_call") == 3


# ---------------------------------------------------------------------------
# fallback telemetry: every plan/policy fallback counted by reason
# ---------------------------------------------------------------------------

def test_fallback_counter_reasons(accl, monkeypatch):
    """accl_cmatmul_fallback_total counts EVERY fused-path fallback
    labelled by reason — what the warn-once log hides (ISSUE r9). An
    explicit overlap=False is a requested XLA pair, never counted."""
    from jax import shard_map
    from accl_tpu.obs import metrics as obs_metrics
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    def trace(overlap, kavail, shape=(16, 64, 64)):
        monkeypatch.setattr(cm, "_kernels_available", lambda: kavail)
        m, k, n = shape

        def body(xs, ws):
            return cm.all_gather_matmul_body(xs, ws, axis="accl",
                                             overlap=overlap)

        jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P("accl"), check_vma=False))(
            jnp.zeros((4 * m, k), jnp.float32),
            jnp.zeros((k, n), jnp.float32))

    def delta(fn):
        before = obs_metrics.snapshot()
        fn()
        d = obs_metrics.delta(before)["counters"]
        return {key: v for key, v in d.items()
                if key.startswith("accl_cmatmul_fallback_total")}

    key = ('accl_cmatmul_fallback_total{op="allgather_matmul",'
           'reason="%s"}')
    # kernels unavailable on the rung -> no_interpret
    d = delta(lambda: trace(True, False))
    assert d.get(key % "no_interpret") == 1
    # session default declined by the size register -> threshold
    saved_th = cm.get_overlap_thresholds()
    try:
        cm.set_overlap_thresholds(1 << 62, 0)
        d = delta(lambda: trace(None, True))
        assert d.get(key % "threshold") == 1
    finally:
        cm.set_overlap_thresholds(*saved_th)
    # overlap requested but no geometry fits ANY arm — k-blocked or
    # n-blocked streaming (the irreducible weight panel) -> vmem_miss
    d = delta(lambda: trace(True, True, shape=(8, 128, 32768)))
    assert d.get(key % "vmem_miss") == 1
    # an explicit overlap=False is a REQUEST, not a fallback — per call
    d = delta(lambda: trace(False, True))
    assert d == {}
    # ... and session-wide (cmatmul_overlap=False): no size register was
    # consulted, so a "threshold" label would be a phantom decline
    saved_ov = cm.get_overlap_enabled()
    try:
        cm.set_overlap_enabled(False)
        d = delta(lambda: trace(None, True))
        assert d == {}
    finally:
        cm.set_overlap_enabled(saved_ov)
    # the warn set dedupes the LOG only; the counter keeps counting
    d = delta(lambda: (trace(True, False), trace(True, False)))
    assert d.get(key % "no_interpret") == 2
    # session hook clears the warn set (ACCL.initialize discipline)
    cm._warned_fallback.add(("x", "y"))
    cm.reset_fallback_warnings()
    assert cm._warned_fallback == set()


# ---------------------------------------------------------------------------
# gathered wgrad body: both orientations vs host math (every rung — the
# kernel-less rung runs the unfused fallback, same math by construction)
# ---------------------------------------------------------------------------

def test_wgrad_body_both_orientations(accl, rng):
    from accl_tpu.parallel.primitives import AXIS, _smap

    comm = _comm(4)
    W, ms, ct, cl = 4, 8, 32, 16
    trav = _ints(rng, (W, ms, ct), lo=-3, hi=4)
    loc = _ints(rng, (W, W * ms, cl), lo=-3, hi=4)

    def run(travel_lhs):
        def body(ts, ls):
            return cm.gathered_wgrad_body(
                ts[0], ls[0], axis=AXIS, travel_lhs=travel_lhs)[None]

        from jax.sharding import PartitionSpec as P
        return np.asarray(_smap(comm, body, 2,
                                in_specs=(P(AXIS), P(AXIS)))(
            _put(comm, trav), _put(comm, loc)))

    gathered = trav.reshape(W * ms, ct).astype(np.float64)
    lhs, rhs = run(True), run(False)
    for r in range(W):
        np.testing.assert_array_equal(
            lhs[r], (gathered.T @ loc[r].astype(np.float64))
            .astype(np.float32))
        np.testing.assert_array_equal(
            rhs[r], (loc[r].astype(np.float64).T @ gathered)
            .astype(np.float32))


def test_wgrad_body_rejects_row_mismatch(accl):
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    def body(ts, ls):
        return cm.gathered_wgrad_body(ts, ls, axis="accl")

    with pytest.raises(ValueError, match="row mismatch"):
        jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P(None), check_vma=False))(
            jnp.zeros((4 * 8, 16), jnp.float32),
            jnp.zeros((3 * 8, 16), jnp.float32))


# ---------------------------------------------------------------------------
# interpreter parity: streaming kernels, fused wgrad, bf16 wire
# (needs simulated remote DMA — skips on rungs without the TPU interpreter)
# ---------------------------------------------------------------------------

def _budget(monkeypatch, nbytes):
    monkeypatch.setattr(cm, "_VMEM_BUDGET", nbytes)


@pytest.mark.parametrize("W", [2, 4, 8])
@pytest.mark.parametrize("bidir", [False, True])
def test_agmm_stream_parity_bit_exact(accl, rng, monkeypatch, W, bidir):
    """k-blocked streaming agmm is bit-exact vs the unfused pair. The
    budget is pinched so modest shapes stream with several k-blocks
    (multi-segment relay + accumulator phases all exercised)."""
    if bidir and W < 4:
        pytest.skip("bidirectional needs P >= 4")
    m, k, n = 16, 512, 128
    _budget(monkeypatch, 192 << 10)
    plan = cm.agmm_plan(m, k, n, W, jnp.float32, bidir)
    assert plan is not None and plan["mode"] == "stream"
    assert plan["nkb"] >= 2
    x = _ints(rng, (W, m, k), lo=-2, hi=3)
    w = _ints(rng, (W, k, n), lo=-2, hi=3)
    comm = _comm(W)
    fused = _run_agmm(comm, x, w, Algorithm.PALLAS, bidir)
    ref = _run_agmm(comm, x, w, Algorithm.XLA, bidir)
    np.testing.assert_array_equal(fused, ref)


def test_agmm_stream_parity_real_budget(accl, rng):
    """The acceptance shape: a shard whose RESIDENT plan misses the real
    12 MiB budget (w block alone is 16 MiB) — previously fell back to
    XLA, now streams — bit-exact vs the unfused pair at W=2."""
    m, k, n = 16, 32768, 128
    plan = cm.agmm_plan(m, k, n, 2, jnp.float32, False)
    assert plan is not None and plan["mode"] == "stream"
    x = _ints(rng, (2, m, k), lo=-1, hi=2)
    w = _ints(rng, (2, k, n), lo=-1, hi=2)
    comm = _comm(2)
    fused = _run_agmm(comm, x, w, Algorithm.PALLAS, False)
    ref = _run_agmm(comm, x, w, Algorithm.XLA, False)
    np.testing.assert_array_equal(fused, ref)


@pytest.mark.parametrize("W", [2, 4, 8])
@pytest.mark.parametrize("bidir", [False, True])
def test_mmrs_stream_parity_bit_exact(accl, rng, monkeypatch, W, bidir):
    if bidir and W < 4:
        pytest.skip("bidirectional needs P >= 4")
    m, k, n = 16, 512, 128
    _budget(monkeypatch, 192 << 10)
    plan = cm.mmrs_plan(W * m, k, n, W, jnp.float32, bidir)
    assert plan is not None and plan["mode"] == "stream"
    assert plan["nkb"] >= 2
    x = _ints(rng, (W, W * m, k), lo=-2, hi=3)
    w = _ints(rng, (W, k, n), lo=-2, hi=3)
    comm = _comm(W)
    fused = _run_mmrs(comm, x, w, Algorithm.PALLAS, bidir)
    ref = _run_mmrs(comm, x, w, Algorithm.XLA, bidir)
    np.testing.assert_array_equal(fused, ref)


def test_stream_race_free(accl, rng, monkeypatch):
    """The streaming kernels under the interpret-mode race detector:
    the segment-level credit protocol (grants == gates, store-and-
    forward relay, accumulator phase flushes) must hold."""
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(
        pallas_ring, "_interpret_params",
        lambda: pltpu.InterpretParams(detect_races=True))
    _budget(monkeypatch, 192 << 10)
    W, m, k, n = 4, 16, 512, 128
    comm = _comm(W)
    x_ag = _ints(rng, (W, m, k), lo=-2, hi=3)
    x_rs = _ints(rng, (W, W * m, k), lo=-2, hi=3)
    w = _ints(rng, (W, k, n), lo=-2, hi=3)
    for bidir in (False, True):
        assert cm.agmm_plan(m, k, n, W, jnp.float32, bidir)["mode"] \
            == "stream"
        fused = _run_agmm(comm, x_ag, w, Algorithm.PALLAS, bidir)
        np.testing.assert_array_equal(
            fused, _run_agmm(comm, x_ag, w, Algorithm.XLA, bidir))
        fused = _run_mmrs(comm, x_rs, w, Algorithm.PALLAS, bidir)
        np.testing.assert_array_equal(
            fused, _run_mmrs(comm, x_rs, w, Algorithm.XLA, bidir))


@pytest.mark.parametrize("W", [2, 4, 8])
def test_fused_wgrad_parity_bit_exact(accl, rng, W):
    """The fused dgrad/wgrad backward matches the unfused VJP bit-exact
    (integer-valued operands): grads through both custom VJPs with the
    fused dw kernels engaged vs the overlap=False unfused rendition."""
    from accl_tpu.parallel.primitives import AXIS, _smap

    comm = _comm(W)
    m, k, n = 8, 64, 32
    x = _ints(rng, (W, m, k), lo=-2, hi=3)
    w = _ints(rng, (W, k, n), lo=-2, hi=3)

    def make(overlap):
        def body(xs, ws):
            def loss(ws_):
                y = cm.all_gather_matmul(xs[0], ws_, AXIS, None, overlap)
                z = cm.matmul_reduce_scatter(
                    y.astype(xs.dtype), jnp.transpose(ws_), AXIS, None,
                    overlap)
                return jnp.sum(z)

            return jax.grad(loss)(ws[0])[None]

        return _smap(comm, body, 2)

    g_fused = np.asarray(make(True)(_put(comm, x), _put(comm, w)))
    g_ref = np.asarray(make(False)(_put(comm, x), _put(comm, w)))
    np.testing.assert_array_equal(g_fused, g_ref)


def test_wgrad_race_free(accl, rng, monkeypatch):
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P

    from accl_tpu.parallel.primitives import AXIS, _smap

    monkeypatch.setattr(
        pallas_ring, "_interpret_params",
        lambda: pltpu.InterpretParams(detect_races=True))
    W, ms, ct, cl = 8, 16, 128, 64
    comm = _comm(W)
    trav = _ints(rng, (W, ms, ct), lo=-2, hi=3)
    loc = _ints(rng, (W, W * ms, cl), lo=-2, hi=3)
    for bidir in (False, True):
        for lhs in (True, False):
            def body(ts, ls, lhs=lhs, bidir=bidir):
                return cm.gathered_wgrad_body(
                    ts[0], ls[0], axis=AXIS, overlap=True,
                    bidirectional=bidir, travel_lhs=lhs)[None]

            got = np.asarray(_smap(comm, body, 2,
                                   in_specs=(P(AXIS), P(AXIS)))(
                _put(comm, trav), _put(comm, loc)))
            gathered = trav.reshape(W * ms, ct).astype(np.float64)
            for r in range(W):
                want = (gathered.T @ loc[r].astype(np.float64) if lhs
                        else loc[r].astype(np.float64).T @ gathered)
                np.testing.assert_array_equal(
                    got[r], want.astype(np.float32))


def test_agmm_wire_bit_exact_with_f32_accumulate(accl, rng):
    """bf16 wire staging for agmm rounds the INPUT shard once: with
    small-integer operands (exactly bf16-representable) the fused wire
    path is bit-exact vs the full-precision pair, while the partial
    sums exceed bf16's 8-bit-mantissa exact range — so an exact result
    PROVES the accumulation ran wider than the wire (f32 on-chip)."""
    W, m, k, n = 4, 16, 512, 128
    comm = _comm(W)
    # |entries| <= 3: bf16-lossless on the wire. k=512 terms of up to 9
    # push partial sums past 256 — bf16 accumulation would round them.
    x = _ints(rng, (W, m, k), lo=-3, hi=4)
    w = _ints(rng, (W, k, n), lo=-3, hi=4)
    prog = algorithms.build_allgather_matmul(
        comm, Algorithm.PALLAS, bidirectional=True, wire_dtype="bf16")
    fused = np.asarray(prog(_put(comm, x), _put(comm, w)))
    ref = _run_agmm(comm, x, w, Algorithm.XLA, True)
    assert np.abs(ref).max() > 256        # sums overflow bf16 exactness
    np.testing.assert_array_equal(fused, ref)


def test_mmrs_wire_tolerance(accl, rng):
    """bf16 wire staging for mmrs rounds the travelling PARTIAL SUM once
    per hop — tolerance-bounded vs the f32 pair (docs/kernels.md states
    the bound), and exact when every travelling partial is
    bf16-representable."""
    W, m, k, n = 4, 16, 64, 32
    comm = _comm(W)
    x = rng.standard_normal((W, W * m, k)).astype(np.float32)
    w = rng.standard_normal((W, k, n)).astype(np.float32)
    prog = algorithms.build_matmul_reduce_scatter(
        comm, Algorithm.PALLAS, bidirectional=True, wire_dtype="bf16")
    fused = np.asarray(prog(_put(comm, x), _put(comm, w)))
    ref = _run_mmrs(comm, x, w, Algorithm.XLA, True)
    # P-1 bf16 roundings of travelling partials: relative error bounded
    # by ~(P-1) * 2^-8 on the partial scale
    np.testing.assert_allclose(fused, ref, rtol=0.05,
                               atol=0.05 * np.abs(ref).max())
    # tiny integers: every travelling partial stays bf16-exact
    xi = _ints(rng, (W, W * m, 8), lo=-1, hi=2)[:, :, :8]
    wi = _ints(rng, (W, 8, n), lo=-1, hi=2)
    prog = algorithms.build_matmul_reduce_scatter(
        comm, Algorithm.PALLAS, bidirectional=False, wire_dtype="bf16")
    fused = np.asarray(prog(_put(comm, xi), _put(comm, wi)))
    ref = _run_mmrs(comm, xi, wi, Algorithm.XLA, False)
    np.testing.assert_array_equal(fused, ref)


# ---------------------------------------------------------------------------
# mlp wire thread-through (every rung)
# ---------------------------------------------------------------------------

def test_mlp_wire_dtype_threads(rng):
    """make_train_step(wire_dtype=...) builds and trains; on rungs where
    the fused kernels cannot run the wire request is moot (full-
    precision psum baseline), so the trajectories agree exactly."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from accl_tpu.models import mlp

    d, h, b = 16, 64, 8
    mesh = mlp.make_mesh(jax.devices()[:4], dp=1, tp=4)
    params = mlp.shard_params(
        mlp.init_params(jax.random.PRNGKey(1), d, h), mesh)
    sh = NamedSharding(mesh, P(mlp.DP_AXIS, None))
    x = jax.device_put(rng.standard_normal((b, d)).astype(np.float32), sh)
    t = jax.device_put(rng.standard_normal((b, d)).astype(np.float32), sh)
    traj = {}
    for wd in ("off", "bf16"):
        p = params
        step = mlp.make_train_step(mesh, lr=5e-2, overlap=None,
                                   wire_dtype=wd)
        traj[wd] = []
        for _ in range(3):
            p, loss = step(p, x, t)
            traj[wd].append(float(loss))
    if not cm._kernels_available():
        np.testing.assert_array_equal(traj["off"], traj["bf16"])
    else:
        np.testing.assert_allclose(traj["off"], traj["bf16"],
                                   rtol=0.05, atol=1e-3)


def test_wgrad_wire_traces(accl, monkeypatch):
    """bf16 wire on the wgrad path: the travelling shard is cast once
    (hp_compression lane) and the in-kernel contribution up-converts at
    the fold — lax.dot_general requires matching operand dtypes, so a
    bf16 arrival meeting a f32 local block must cast inside the kernel
    (regression: round-9 review)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))
    for lhs in (True, False):
        def body(ts, ls, lhs=lhs):
            return cm.gathered_wgrad_body(
                ts, ls, axis="accl", overlap=True, wire_dtype="bf16",
                travel_lhs=lhs)

        t = str(jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P(None), check_vma=False))(
            jnp.zeros((4 * 16, 64), jnp.float32),
            jnp.zeros((4 * 16, 32), jnp.float32)))
        assert t.count("pallas_call") == 2   # cast lane + wgrad kernel


# ---------------------------------------------------------------------------
# stochastic-rounding wire codec (round 10): "bf16_sr" as a cmatmul/a2a
# wire dtype — the ROADMAP round-9 leftover
# ---------------------------------------------------------------------------

def test_wire_sr_codec_resolution(accl):
    """"bf16_sr" is a full wire codec: accepted by the session register
    (write-through), sized like bf16 everywhere (plans, effective wire
    bytes, select()), and resolved to (bfloat16, stochastic=True) for
    the input-shard casts."""
    from accl_tpu.config import TransportBackend
    from accl_tpu.constants import operation

    assert cm._resolve_wire_codec("bf16_sr", jnp.float32) == \
        (jnp.bfloat16, True)
    assert cm._resolve_wire_codec("bf16", jnp.float32) == \
        (jnp.bfloat16, False)
    # never upcasts, SR or not
    assert cm._resolve_wire_codec("bf16_sr", jnp.bfloat16) == (None, False)
    assert cm.wire_itemsize(jnp.float32, "bf16_sr") == 2
    saved = accl.config
    try:
        accl.config = accl.config.replace(cmatmul_wire_dtype="bf16_sr")
        assert cm.get_wire_dtype() == "bf16_sr"
        assert cm._resolve_wire(None, jnp.float32) == jnp.bfloat16
        assert cm._resolve_wire_codec(None, jnp.float32)[1] is True
        # select() scales the matmul ops' bytes exactly as under bf16
        ici = accl.config.replace(transport=TransportBackend.ICI)
        th = ici.ag_matmul_threshold
        assert algorithms.select(operation.allgather_matmul, th, comm=accl
                                 .global_comm(), cfg=ici) == Algorithm.XLA
        assert algorithms.select(operation.allgather_matmul, 2 * th,
                                 comm=accl.global_comm(),
                                 cfg=ici) == Algorithm.PALLAS
        assert algorithms.cmatmul_wire_bytes(
            operation.allgather_matmul, 1024, ici) == 512
    finally:
        accl.config = saved
    with pytest.raises(ValueError, match="wire dtype"):
        cm.set_wire_dtype("bf16_sr_typo")
    with pytest.raises(ValueError, match="wire dtype"):
        cm._resolve_wire_codec("fp16_sr", jnp.float32)


def test_wire_sr_cast_bounded_bias(rng):
    """The SR compress lane's parity contract vs the deterministic cast:
    every SR output is one of the two bf16 neighbors of its input (a
    rounding, never a perturbation), and the MEAN rounding bias over
    repeated compression is bounded by the deterministic cast's. Off
    TPU the lane degrades to the deterministic cast (TPU PRNG
    unavailable) — the bias bound then holds with equality."""
    x = (rng.standard_normal((64, 128)).astype(np.float32)
         * (1.0 + 2 ** -9))   # off the bf16 grid: rounding must happen
    det = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    on_tpu = jax.default_backend() == "tpu"
    from accl_tpu.ops import compression

    seeds = range(8) if on_tpu else (0,)
    outs = []
    for s in seeds:
        sr = np.asarray(compression.pallas_compress_stochastic(
            jnp.asarray(x), jnp.bfloat16, seed=s).astype(jnp.float32))
        outs.append(sr)
        # each element is a bf16 NEIGHBOR of x: |sr - x| <= one bf16 ulp
        ulp = np.maximum(np.abs(x) * 2 ** -7, np.finfo(np.float32).tiny)
        assert np.all(np.abs(sr - x) <= ulp)
    mean_sr = np.mean(outs, axis=0)
    det_bias = abs(float(np.mean(det - x)))
    sr_bias = abs(float(np.mean(mean_sr - x)))
    if on_tpu:
        # unbiasedness: averaged over seeds, SR's bias must not exceed
        # the deterministic cast's (it converges to zero)
        assert sr_bias <= det_bias + 1e-6
    else:
        np.testing.assert_array_equal(mean_sr, det)   # documented degrade


def test_wire_sr_threads_through_kernels(accl, monkeypatch):
    """bf16_sr reaches the agmm/wgrad staged-cast path: on-TPU it adds
    the SR cast kernel; off-TPU the cast degrades to a plain astype, so
    only the ring kernel traces — either way the ring kernel engages
    with half-width staging exactly as under bf16."""
    on_tpu = jax.default_backend() == "tpu"
    casts = 1 if on_tpu else 0
    t = _trace_body(monkeypatch,
                    lambda xs, ws: cm.all_gather_matmul_body(
                        xs, ws, axis="accl", overlap=True,
                        wire_dtype="bf16_sr"),
                    (4 * 16, 128), (128, 128))
    assert t.count("pallas_call") == 1 + casts
    for lhs in (True, False):
        def body(ts, ls, lhs=lhs):
            return cm.gathered_wgrad_body(
                ts, ls, axis="accl", overlap=True, wire_dtype="bf16_sr",
                travel_lhs=lhs)

        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))
        t = str(jax.make_jaxpr(shard_map(
            body, mesh=mesh, in_specs=(P("accl"), P(None)),
            out_specs=P(None), check_vma=False))(
            jnp.zeros((4 * 16, 64), jnp.float32),
            jnp.zeros((4 * 16, 32), jnp.float32)))
        assert t.count("pallas_call") == 1 + casts


# ---------------------------------------------------------------------------
# round 20: n-blocked streaming plans — the accumulator-floor arm
# (parity needs simulated remote DMA; the trace/plan tests run anywhere)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("W", [2, 4, 8])
@pytest.mark.parametrize("bidir", [False, True])
def test_agmm_nblock_parity_bit_exact(accl, rng, monkeypatch, W, bidir):
    """m-blocked streaming agmm (the accumulator-floor arm) is
    bit-exact vs the unfused pair: the budget is pinched so even the
    128-lane k-block misses and the plan splits the traveller's rows
    (nmb blocks, each its own ring pass over nkb k-segments)."""
    if bidir and W < 4:
        pytest.skip("bidirectional needs P >= 4")
    m, k, n = 256, 256, 128
    _budget(monkeypatch, 128 << 10)
    plan = cm.agmm_plan(m, k, n, W, jnp.float32, bidir)
    assert plan is not None and plan["mode"] == "stream"
    assert plan["nmb"] >= 2 and plan["nkb"] >= 2
    x = _ints(rng, (W, m, k), lo=-2, hi=3)
    w = _ints(rng, (W, k, n), lo=-2, hi=3)
    comm = _comm(W)
    fused = _run_agmm(comm, x, w, Algorithm.PALLAS, bidir)
    ref = _run_agmm(comm, x, w, Algorithm.XLA, bidir)
    np.testing.assert_array_equal(fused, ref)


@pytest.mark.parametrize("W", [2, 4, 8])
@pytest.mark.parametrize("bidir", [False, True])
def test_mmrs_nblock_parity_bit_exact(accl, rng, monkeypatch, W, bidir):
    """n-blocked streaming mmrs: the travelling accumulator's columns
    split into nnb blocks, each riding its own ring over the streamed
    x grid and a w column slice — bit-exact vs the unfused pair."""
    if bidir and W < 4:
        pytest.skip("bidirectional needs P >= 4")
    m, k, n = 16, 256, 512
    _budget(monkeypatch, 128 << 10)
    plan = cm.mmrs_plan(W * m, k, n, W, jnp.float32, bidir)
    assert plan is not None and plan["mode"] == "stream"
    assert plan["nnb"] >= 2 and plan["nkb"] >= 2
    x = _ints(rng, (W, W * m, k), lo=-2, hi=3)
    w = _ints(rng, (W, k, n), lo=-2, hi=3)
    comm = _comm(W)
    fused = _run_mmrs(comm, x, w, Algorithm.PALLAS, bidir)
    ref = _run_mmrs(comm, x, w, Algorithm.XLA, bidir)
    np.testing.assert_array_equal(fused, ref)


@pytest.mark.parametrize("W", [2, 4, 8])
def test_wgrad_nblock_parity_bit_exact(accl, rng, monkeypatch, W):
    """ct-blocked streaming wgrad: each ctb column block of the
    travelling shard rides its own ring pass into a disjoint dw block —
    bit-exact vs host math in both orientations."""
    from jax.sharding import PartitionSpec as P

    from accl_tpu.parallel.primitives import AXIS, _smap

    ms, ct, cl = 16, 1024, 128
    _budget(monkeypatch, 128 << 10)
    plan = cm.wgrad_plan(ms, ct, cl, W, jnp.float32, jnp.float32, True)
    assert plan is not None and plan["nctb"] >= 2
    comm = _comm(W)
    trav = _ints(rng, (W, ms, ct), lo=-2, hi=3)
    loc = _ints(rng, (W, W * ms, cl), lo=-2, hi=3)
    for lhs in (True, False):
        def body(ts, ls, lhs=lhs):
            return cm.gathered_wgrad_body(
                ts[0], ls[0], axis=AXIS, overlap=True,
                travel_lhs=lhs)[None]

        got = np.asarray(_smap(comm, body, 2,
                               in_specs=(P(AXIS), P(AXIS)))(
            _put(comm, trav), _put(comm, loc)))
        gathered = trav.reshape(W * ms, ct).astype(np.float64)
        for r in range(W):
            want = (gathered.T @ loc[r].astype(np.float64) if lhs
                    else loc[r].astype(np.float64).T @ gathered)
            np.testing.assert_array_equal(got[r], want.astype(np.float32))


def test_nblock_traces_one_kernel_per_block(accl, monkeypatch):
    """The accumulator-floor arm runs the streaming kernel once per
    block: the traced program carries exactly nmb pallas_calls (agmm)
    / nnb (mmrs) — the block loop is unrolled at trace time, so the
    count is the plan's, not a rounding accident."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    _budget(monkeypatch, 128 << 10)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    m, k, n = 256, 256, 128
    plan = cm.agmm_plan(m, k, n, 4, jnp.float32, False)
    assert plan["mode"] == "stream" and plan["nmb"] >= 2
    t = str(jax.make_jaxpr(shard_map(
        lambda xs, ws: cm.all_gather_matmul_body(
            xs, ws, axis="accl", overlap=True, bidirectional=False),
        mesh=mesh, in_specs=(P("accl"), P(None)),
        out_specs=P("accl"), check_vma=False))(
        jnp.zeros((4 * m, k), jnp.float32),
        jnp.zeros((k, n), jnp.float32)))
    assert t.count("pallas_call") == plan["nmb"]

    m, k, n = 16, 256, 512
    plan = cm.mmrs_plan(4 * m, k, n, 4, jnp.float32, False)
    assert plan["mode"] == "stream" and plan["nnb"] >= 2
    t = str(jax.make_jaxpr(shard_map(
        lambda xs, ws: cm.matmul_reduce_scatter_body(
            xs, ws, axis="accl", overlap=True, bidirectional=False),
        mesh=mesh, in_specs=(P("accl"), P(None)),
        out_specs=P("accl"), check_vma=False))(
        jnp.zeros((4 * 4 * m, k), jnp.float32),
        jnp.zeros((k, n), jnp.float32)))
    assert t.count("pallas_call") == plan["nnb"]


def test_nblock_session_register(accl):
    """ACCLConfig.cmatmul_nblock write-through: the accumulator-floor
    arm is a session-selectable register — off pins the honest decline
    (None) for shapes only that arm resolves, the resident and
    k-blocked arms unaffected."""
    shape = (4096, 4096, 4096, 8)
    assert cm.agmm_plan(*shape, jnp.float32, False)["mode"] == "stream"
    saved = accl.config
    try:
        accl.config = accl.config.replace(cmatmul_nblock=False)
        assert not cm.get_nblock_enabled()
        assert cm.agmm_plan(*shape, jnp.float32, False) is None
        # k-blocked streaming (no accumulator floor) stays available
        p = cm.agmm_plan(256, 8192, 512, 8, jnp.float32, False)
        assert p is not None and p["mode"] == "stream"
    finally:
        accl.config = saved
    assert cm.get_nblock_enabled()
