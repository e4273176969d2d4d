"""Layerwise overlapped ZeRO/FSDP (models/zero.py round 11) — the
flagship train step whose parameter gathers ride ``allgather_matmul``
and whose gradient reductions ride ``matmul_reduce_scatter``, plus the
round-11 satellites on the original flat-ravel demo."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accl_tpu.communicator import Communicator
from accl_tpu.models import mlp, zero
from accl_tpu.ops import collective_matmul as cm

WORLD = 8


def _mesh(dp, tp):
    return zero.make_mesh(jax.devices()[:dp * tp], dp, tp)


def _data(rng, rows, d):
    x = rng.standard_normal((rows, d)).astype(np.float32)
    y = rng.standard_normal((rows, d)).astype(np.float32)
    return x, y


# ---------------------------------------------------------------------------
# satellites on the flat-ravel demo
# ---------------------------------------------------------------------------

def test_flat_demo_skips_pad_concat(accl):
    """The demo step pads the gradient vector only when the flat length
    does not divide by world: a divisible geometry must trace NO extra
    concatenate beyond ravel_pytree's own flatten (it used to pay a
    traced concat with a zero-length pad every step)."""
    comm = accl.global_comm()

    def trace(d, h):
        step = zero.build_zero_train_step(comm, d, h)
        state = zero.init_zero_state(jax.random.PRNGKey(0), comm, d, h)
        x = jnp.zeros((WORLD, 4, d), jnp.float32)
        return str(jax.make_jaxpr(step)(state, x, x))

    # n = 2dh + h + d: (16, 32) -> 1072 (divisible by 8), (9, 10) -> 199
    n_nopad = trace(16, 32).count("concatenate")
    n_pad = trace(9, 10).count("concatenate")
    assert n_pad == n_nopad + 1


def test_template_annotation():
    """Satellite: the lru-cached template returns (int, callable) and the
    annotation is a REAL typing.Callable (the old ``callable`` builtin
    inside Tuple[...] was not a type)."""
    import typing

    hints = typing.get_type_hints(zero._template)
    assert hints["return"] == typing.Tuple[int, typing.Callable]
    n, unravel = zero._template(16, 32)
    assert n == 2 * 16 * 32 + 32 + 16 and callable(unravel)


def test_gather_params_rejects_non_addressable(accl):
    """gather_params assembles shards on the HOST; an array spanning
    non-addressable devices (multi-process mesh) must fail with a clear
    NotImplementedError instead of the old opaque np.asarray crash."""
    class _NonAddressable:
        is_fully_addressable = False

    state = zero.ZeroState(w=_NonAddressable(), m=None, v=None, t=None)
    with pytest.raises(NotImplementedError, match="process-addressable"):
        zero.gather_params(state, accl.global_comm(), 16, 32)


def test_zero_single_rank_matches_unsharded_adam():
    """Optimizer-math parity at world=1: every collective is the
    identity, so the sharded step must reproduce an unsharded reference
    Adam step — gradients, BOTH moment updates and the loss bit-exactly
    (any reassociation in the data path or the moment pipeline breaks
    array_equal), the weight itself to a couple of ULPs (the final Adam
    quotient compiles with different FMA contraction in the two
    programs — measured ~6e-8 abs; everything upstream is exact)."""
    from jax.flatten_util import ravel_pytree

    comm1 = Communicator(jax.devices()[:1])
    d, h = 16, 32
    lr, b1, b2, eps = 1e-2, 0.9, 0.999, 1e-8
    key = jax.random.PRNGKey(3)
    state = zero.init_zero_state(key, comm1, d, h)
    step = zero.build_zero_train_step(comm1, d, h, lr=lr)
    n, unravel = zero._template(d, h)
    rng = np.random.default_rng(1)
    x, y = _data(rng, 4, d)
    xs = jax.device_put(x[None], comm1.sharding())
    ys = jax.device_put(y[None], comm1.sharding())

    @jax.jit
    def ref_step(vec, m, v, t):
        loss, grads = jax.value_and_grad(
            lambda p: jnp.mean((mlp.apply(p, x) - y) ** 2))(unravel(vec))
        g = ravel_pytree(grads)[0]
        t_new = t + 1
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * g * g
        mhat = m_new / (1 - b1 ** t_new.astype(jnp.float32))
        vhat = v_new / (1 - b2 ** t_new.astype(jnp.float32))
        return (vec - lr * mhat / (jnp.sqrt(vhat) + eps),
                m_new, v_new, t_new, loss)

    for _ in range(3):
        # rebase the reference on the sharded step's own state each
        # step, so every comparison is one step from IDENTICAL inputs
        # (the ulp on w would otherwise drift the gradients apart)
        prev = state
        state, loss = step(state, xs, ys)
        vec, m, v, t, ref_loss = ref_step(
            jnp.asarray(np.asarray(prev.w).reshape(-1)[:n]),
            jnp.asarray(np.asarray(prev.m).reshape(-1)[:n]),
            jnp.asarray(np.asarray(prev.v).reshape(-1)[:n]),
            prev.t)
        assert float(loss) == float(ref_loss)
        np.testing.assert_array_equal(
            np.asarray(state.m).reshape(-1)[:n], np.asarray(m))
        np.testing.assert_array_equal(
            np.asarray(state.v).reshape(-1)[:n], np.asarray(v))
        np.testing.assert_allclose(
            np.asarray(state.w).reshape(-1)[:n], np.asarray(vec),
            rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# layerwise FSDP: state layout, validation, honesty, trajectories
# ---------------------------------------------------------------------------

def test_init_zero_fsdp_layout(accl):
    """Every parameter (and both Adam moments) lives sharded 1/dp along
    the dp axis — a device block is exactly the agmm travelling shard /
    the flat bucket slice — and the geometry validator rejects shapes
    the shard layout cannot express."""
    dp, tp = 2, 2
    mesh = _mesh(dp, tp)
    L, d, h, H = 2, 16, 32, 4
    st = zero.init_zero_fsdp(jax.random.PRNGKey(0), mesh, L, d, h, H)
    dtp, q_rows, q_rows_pad = zero._attn_travel_sizes(d, tp, dp)
    assert len(st.p.wqkvt) == L
    assert st.p.wqkvt[0].shape == (tp * q_rows_pad, d)
    assert st.p.wot[0].shape == (d, d)
    assert st.p.w1t[0].shape == (h, d)
    assert st.p.w2t[0].shape == (d, h)
    # device blocks: the travel shards
    assert st.p.wqkvt[0].addressable_shards[0].data.shape == \
        (q_rows_pad // dp, d)
    assert st.p.wot[0].addressable_shards[0].data.shape == \
        (d // dp, d // tp)
    assert st.p.w1t[0].addressable_shards[0].data.shape == \
        (h // (tp * dp), d)
    assert st.p.w2t[0].addressable_shards[0].data.shape == \
        (d // dp, h // tp)
    for tree in (st.m, st.v):
        assert jax.tree_util.tree_structure(tree) == \
            jax.tree_util.tree_structure(st.p)
        assert all(float(jnp.sum(jnp.abs(leaf))) == 0.0
                   for leaf in jax.tree_util.tree_leaves(tree))
    with pytest.raises(ValueError, match="n_heads"):
        zero.init_zero_fsdp(jax.random.PRNGKey(0), mesh, 1, 18, 32, 4)
    with pytest.raises(ValueError, match="tp"):
        # heads divide d_model but not tp=2
        zero.init_zero_fsdp(jax.random.PRNGKey(0), mesh, 1, 15, 32, 3)
    with pytest.raises(ValueError, match="dp"):
        # hidden/tp = 6, not divisible by dp=4
        zero.init_zero_fsdp(jax.random.PRNGKey(0), _mesh(4, 2), 1, 16,
                            12, 4)


def test_fsdp_commit_honesty(accl, monkeypatch):
    """The layerwise step COMMITS to the flat-ravel baseline when the
    per-layer plans cannot engage — never a degraded unfused layerwise
    rendition — and the decline is counted under op="zero_fsdp" with
    the exact resolution reason. An explicit/session overlap-off is a
    requested baseline, never counted."""
    from accl_tpu.obs import metrics as obs_metrics

    mesh = _mesh(2, 2)
    L, d, h, H = 2, 16, 32, 4
    st = zero.init_zero_fsdp(jax.random.PRNGKey(0), mesh, L, d, h, H)
    rng = np.random.default_rng(0)
    x, y = _data(rng, 16, d)

    def run(**kw):
        step = zero.build_zero_fsdp_train_step(mesh, L, d, h, H, **kw)
        return step(st, x, y)

    def fallback_delta(fn):
        before = obs_metrics.snapshot()
        out = fn()
        delta = obs_metrics.delta(before)["counters"]
        return out, {k: v for k, v in delta.items()
                     if k.startswith('accl_cmatmul_fallback_total'
                                     '{op="zero_fsdp"')}

    key = 'accl_cmatmul_fallback_total{op="zero_fsdp",reason="%s"}'
    # this rung: kernels unavailable -> committed baseline, counted once
    (st_f, loss_f), d1 = fallback_delta(lambda: run(overlap=True))
    if cm._kernels_available():
        pytest.skip("kernels available here: the committed-fallback "
                    "rung behavior is not observable")
    assert d1.get(key % "no_interpret") == 1
    (st_b, loss_b), d0 = fallback_delta(lambda: run(overlap=False))
    assert d0 == {}                      # a requested baseline: no count
    assert float(loss_f) == float(loss_b)
    np.testing.assert_array_equal(np.asarray(st_f.p.w1t[0]),
                                  np.asarray(st_b.p.w1t[0]))
    # session register declines at overlap=None -> threshold reason
    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    saved = cm.get_overlap_thresholds()
    try:
        cm.set_overlap_thresholds(1 << 62, 1 << 62)
        _, d2 = fallback_delta(lambda: run())
        assert d2.get(key % "threshold") == 1
    finally:
        cm.set_overlap_thresholds(*saved)
    # session zero_overlap=False is a requested baseline too
    saved_ov = zero.get_overlap_enabled()
    try:
        zero.set_overlap_enabled(False)
        _, d3 = fallback_delta(lambda: run())
        assert d3 == {}
    finally:
        zero.set_overlap_enabled(saved_ov)


def test_fsdp_engage_covers_wgrad_plans(accl, monkeypatch):
    """The commit resolution consults ALL SIX per-layer kernel plans: a
    geometry whose agmm/mmrs plans fit VMEM (resident or n-blocked
    streaming) but whose fused-wgrad dw panel misses even the
    ctb-streaming arm (the per-channel local block is its irreducible
    term) must decline the WHOLE commit — the step would otherwise run
    a "fused" schedule with its activation gradients silently unfused,
    against the never-degraded policy."""
    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    d, h, b, dp = 2048, 1024, 8192, 4
    f32 = jnp.float32
    assert cm.agmm_engage_reason(h // dp, d, b, dp, f32, True) is None
    assert cm.agmm_engage_reason(d // dp, h, b, dp, f32, True) is None
    assert cm.mmrs_engage_reason(h, b, d, dp, f32, True) is None
    assert cm.mmrs_engage_reason(d, b, h, dp, f32, True) is None
    assert cm.wgrad_engage_reason(h // dp, d, b, dp, f32,
                                  True) == "vmem_miss"
    assert zero.fsdp_engage_reason(d, h, b, dp, 1,
                                   overlap=True) == "vmem_miss"
    # the attention resolver runs the same six-plan discipline over the
    # Wqkvᵀ/Woᵀ travel shards — the same wgrad panel miss declines it
    assert zero.fsdp_attn_engage_reason(d, b, dp, 1,
                                        overlap=True) == "vmem_miss"
    # the flagship AOT geometry clears all twelve resolutions
    assert zero.fsdp_engage_reason(256, 1024, 128, 4, 2,
                                   overlap=True) is None
    assert zero.fsdp_attn_engage_reason(256, 128, 4, 2,
                                        overlap=True) is None


def test_fsdp_config_write_through(accl):
    """ACCLConfig.zero_overlap / zero_prefetch land in the model module
    at EVERY config assignment (the cmatmul_overlap shape)."""
    saved = accl.config
    try:
        accl.config = accl.config.replace(zero_overlap=False,
                                          zero_prefetch=False)
        assert not zero.get_overlap_enabled()
        assert not zero.get_prefetch_enabled()
        accl.config = accl.config.replace(zero_overlap=True,
                                          zero_prefetch=True)
        assert zero.get_overlap_enabled()
        assert zero.get_prefetch_enabled()
    finally:
        accl.config = saved


@pytest.mark.parametrize("world", [2, 4, 8])
def test_fsdp_loss_trajectory_overlap_ab(accl, rng, world):
    """Training through the layerwise builder produces the same loss
    trajectory with the fused datapath requested vs the flat baseline
    pinned — selectable per build. On rungs where the kernels cannot
    run both builds COMMIT to the identical flat program (bit-exact);
    where they can, the fused schedule matches to float tolerance."""
    mesh = _mesh(world, 1)
    L, d, h, H = 2, 16, 32, 2
    st = zero.init_zero_fsdp(jax.random.PRNGKey(1), mesh, L, d, h, H)
    b = 8 * world
    x, y = _data(rng, b, d)
    fused = zero.build_zero_fsdp_train_step(mesh, L, d, h, H,
                                            overlap=True)
    flat = zero.build_zero_fsdp_train_step(mesh, L, d, h, H,
                                           overlap=False)
    engaged = zero.fsdp_engages(d, h, b // world, world, 1, overlap=True)
    st_a, st_b = st, st
    losses_a, losses_b = [], []
    for _ in range(3):
        st_a, la = fused(st_a, x, y)
        st_b, lb = flat(st_b, x, y)
        losses_a.append(float(la))
        losses_b.append(float(lb))
    if engaged:
        np.testing.assert_allclose(losses_a, losses_b, rtol=2e-5)
    else:
        assert losses_a == losses_b          # same committed program
    assert losses_b[-1] < losses_b[0]        # it actually trains
    # the optimizer state stays sharded 1/dp between steps
    assert st_b.p.w1t[0].addressable_shards[0].data.shape == \
        (h // world, d)


def test_fsdp_tp_invariance(accl, rng):
    """The SAME model (same init key, same global weights) trains to the
    same losses under (dp=2, tp=1) and (dp=2, tp=2) — the Megatron
    split is a layout, not a math change."""
    L, d, h, H = 1, 16, 32, 4
    x, y = _data(rng, 16, d)
    losses = {}
    for tp in (1, 2):
        mesh = _mesh(2, tp)
        st = zero.init_zero_fsdp(jax.random.PRNGKey(5), mesh, L, d, h, H)
        step = zero.build_zero_fsdp_train_step(mesh, L, d, h, H,
                                               overlap=False)
        ls = []
        for _ in range(2):
            st, loss = step(st, x, y)
            ls.append(float(loss))
        losses[tp] = ls
    np.testing.assert_allclose(losses[1], losses[2], rtol=2e-5)


# ---------------------------------------------------------------------------
# trace-level coverage: the fused schedule's kernels on every rung
# (tracing a pallas_call runs the whole kernel Python abstractly)
# ---------------------------------------------------------------------------

def _fused_trace(monkeypatch, L=2, d=16, h=32, H=4, rows=16, **kw):
    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = _mesh(2, 2)
    st = zero.init_zero_fsdp(jax.random.PRNGKey(0), mesh, L, d, h, H)
    step = zero.build_zero_fsdp_train_step(mesh, L, d, h, H,
                                           overlap=True, **kw)
    x = jnp.zeros((rows, d), jnp.float32)
    return str(jax.make_jaxpr(lambda s, a, b: step(s, a, b))(st, x, x))


def test_fsdp_traces_twelve_kernels_per_layer(accl, monkeypatch):
    """The fully-fused train step traces TWELVE collective-matmul
    kernels per layer — the attention projections ride the SAME agmm
    family as the MLP: 4 forward agmm parameter gathers (Wqkvᵀ, Woᵀ,
    W1ᵀ, W2ᵀ), their 4 dual mmrs gradient reductions, and 4 fused
    gathered-wgrad activation-gradient kernels (the backward parameter
    re-gather folded into the contraction). No unfused collective
    survives in the traced program."""
    L = 2
    t = _fused_trace(monkeypatch, L=L)
    assert t.count("pallas_call") == 12 * L
    assert "all_gather" not in t
    assert "all_to_all" not in t


def test_fsdp_traces_flash_kernels(accl, monkeypatch):
    """At a flash-tileable sequence (S % 128 == 0) the step composes
    flash and cmatmul in ONE program: + fwd and fused-bwd flash kernels
    per layer on top of the 12 collective matmuls."""
    t = _fused_trace(monkeypatch, L=1, rows=256)   # 128 rows per dp rank
    assert t.count("pallas_call") == 12 + 2


def test_fsdp_wire_traces_more_kernels(accl, monkeypatch):
    """bf16 wire staging adds the hp_compression cast lanes (shard
    casts + the bucketized gradient leg) on top of the base kernels."""
    base = _fused_trace(monkeypatch).count("pallas_call")
    wired = _fused_trace(monkeypatch,
                         wire_dtype="bf16").count("pallas_call")
    assert wired > base


def test_fsdp_prefetch_counters(accl, monkeypatch):
    """Cross-layer prefetch accounting rides the PREFETCHED-BUCKET
    attention tier: when the attention plans decline (here a session
    size threshold the smaller Wqkvᵀ payload misses while the MLP legs
    clear it) the build counts one hit (layer l+1's bucket gather
    issued under layer l's compute) or one decline when prefetch is
    off — at trace/build time, like the fallback counters. The
    fully-fused tier has no gathers left to prefetch and counts
    nothing."""
    from accl_tpu.obs import metrics as obs_metrics

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = _mesh(4, 2)
    d, h, H = 256, 512, 4

    def delta(L=2, **kw):
        st = zero.init_zero_fsdp(jax.random.PRNGKey(0), mesh, L, d, h,
                                 H)
        step = zero.build_zero_fsdp_train_step(mesh, L, d, h, H, **kw)
        x = jnp.zeros((128, d), jnp.float32)
        before = obs_metrics.snapshot()
        jax.make_jaxpr(lambda s, a, b: step(s, a, b))(st, x, x)
        d_ = obs_metrics.delta(before)["counters"]
        return {k: v for k, v in d_.items()
                if k.startswith("accl_zero_prefetch_total")}

    hit = 'accl_zero_prefetch_total{event="hit"}'
    dec = 'accl_zero_prefetch_total{event="decline"}'
    saved = cm.get_overlap_thresholds()
    try:
        # attention agmm payloads sit under 40 KB at this geometry, the
        # MLP legs above it: the step commits to the tier-2 schedule
        cm.set_overlap_thresholds(40000, 0)
        assert delta(L=2) == {hit: 1}
        assert delta(L=2, prefetch=False) == {dec: 1}
        assert delta(L=1) == {}             # nothing to prefetch
    finally:
        cm.set_overlap_thresholds(*saved)
    # fully-fused tier: attention rides agmm, nothing to prefetch
    assert delta(L=2, overlap=True) == {}


# ---------------------------------------------------------------------------
# the fsdp_matmul entry point / builder (the FSDP forward as a program)
# ---------------------------------------------------------------------------

def test_fsdp_matmul_builder_parity(accl, rng):
    """build_fsdp_matmul's XLA path computes x @ all_gather(wt)ᵀ — the
    ZeRO forward — against host math; the PALLAS path traces the agmm
    kernel on the travelling WEIGHT shard."""
    from accl_tpu import Algorithm
    from accl_tpu.parallel import algorithms

    comm = accl.global_comm()
    W = comm.world_size
    m, k, n = 8, 16, 32
    assert n % W == 0
    x = rng.standard_normal((W, m, k)).astype(np.float32)
    wt = rng.standard_normal((W, n // W, k)).astype(np.float32)
    prog = algorithms.build_fsdp_matmul(comm, Algorithm.XLA)
    out = np.asarray(prog(jax.device_put(x, comm.sharding()),
                          jax.device_put(wt, comm.sharding())))
    w_full = wt.reshape(n, k)
    for r in range(W):
        np.testing.assert_allclose(out[r], x[r] @ w_full.T,
                                   rtol=1e-5, atol=1e-5)


def test_device_api_fsdp_matmul_traces_kernel(accl, monkeypatch):
    """device_api.fsdp_matmul rides the agmm kernel when overlap is
    forced (the gather IS the matmul), and its VJP traces the dual
    mmrs + wgrad kernels — the whole FSDP communication pattern."""
    from accl_tpu import device_api as dapi
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    monkeypatch.setattr(cm, "_kernels_available", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("accl",))

    def body(xs, ws):
        def loss(w_):
            return jnp.sum(dapi.fsdp_matmul(xs, w_, axis="accl",
                                            overlap=True))
        return jax.grad(loss)(ws)

    t = str(jax.make_jaxpr(shard_map(
        body, mesh=mesh, in_specs=(P(None), P("accl")),
        out_specs=P("accl"), check_vma=False))(
        jnp.zeros((8, 16), jnp.float32),
        jnp.zeros((4 * 8, 16), jnp.float32)))
    assert t.count("pallas_call") == 3   # fwd agmm + bwd mmrs + wgrad


# ---------------------------------------------------------------------------
# interpret-RDMA rung: the fused schedule actually executes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world", [2, 4, 8])
def test_fsdp_fused_parity_interpret(accl, rng, world):
    """On rungs whose interpreter simulates remote DMA the fused
    layerwise schedule EXECUTES: with wire staging off its loss
    trajectory matches the flat-ravel baseline to float tolerance at
    worlds {2, 4, 8} (every collective reassociates the same sums)."""
    mesh = _mesh(world, 1)
    L, d, h, H = 2, 16, 32, 2
    st = zero.init_zero_fsdp(jax.random.PRNGKey(2), mesh, L, d, h, H)
    b = 8 * world
    x, y = _data(rng, b, d)
    assert zero.fsdp_engages(d, h, b // world, world, 1, overlap=True)
    fused = zero.build_zero_fsdp_train_step(mesh, L, d, h, H,
                                            overlap=True,
                                            wire_dtype="off")
    flat = zero.build_zero_fsdp_train_step(mesh, L, d, h, H,
                                           overlap=False)
    st_a, st_b = st, st
    for _ in range(3):
        st_a, la = fused(st_a, x, y)
        st_b, lb = flat(st_b, x, y)
        np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st_a.p.w1t[0]),
                               np.asarray(st_b.p.w1t[0]),
                               rtol=1e-4, atol=1e-5)


def test_fsdp_bf16_wire_tolerance_interpret(accl, rng):
    """bf16 wire staging on the fused legs + the bucketized gradient leg
    stays tolerance-bounded vs the full-precision fused run."""
    world = 4
    mesh = _mesh(world, 1)
    L, d, h, H = 2, 16, 32, 2
    st = zero.init_zero_fsdp(jax.random.PRNGKey(4), mesh, L, d, h, H)
    x, y = _data(rng, 8 * world, d)
    full = zero.build_zero_fsdp_train_step(mesh, L, d, h, H,
                                           overlap=True,
                                           wire_dtype="off")
    wired = zero.build_zero_fsdp_train_step(mesh, L, d, h, H,
                                            overlap=True,
                                            wire_dtype="bf16")
    st_a, st_b = st, st
    for _ in range(2):
        st_a, la = full(st_a, x, y)
        st_b, lb = wired(st_b, x, y)
        np.testing.assert_allclose(float(la), float(lb),
                                   rtol=2e-2, atol=2e-2)
