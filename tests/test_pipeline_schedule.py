"""1F1B pipeline parallelism: schedule tables, train-step parity, the
Pallas activation relay, and the composed (pp, dp, tp) step.

Ladder rungs covered here:

* **host**: the lockstep simulator's tables (every work unit exactly
  once, dependencies respected, the O(world) stash bound, bubble
  accounting) and the degenerate-geometry ValueError;
* **emulator (CPU shard_map)**: loss-trajectory parity — 1F1B vs the
  GPipe oracle vs a float64 host reference — at worlds {2, 4}, plain
  and interleaved, plus the composed transformer step on pp x dp and
  pp x tp meshes; relay VJP parity; fallback/commit-honesty counting;
* **interpret**: the relay kernel under the TPU interpreter's race
  detector;
* **AOT v5e:2x4**: the relay kernel and the composed fused step lower
  to Mosaic kernels for real hardware (the *_schedule pin discipline).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from accl_tpu.communicator import Communicator
from accl_tpu.models import pipeline as pp
from accl_tpu.obs import metrics
from accl_tpu.ops import pipeline_relay as relay


def _counter(snap_text: str, needle: str) -> bool:
    return needle in snap_text


def _sub_comm(world: int) -> Communicator:
    return Communicator(jax.devices()[:world])


def _pp_io(comm, M, n, d, rng):
    """(x, y) global (world, M, n, d) arrays: rank 0 feeds, last rank
    holds targets."""
    W = comm.world_size
    xm = rng.standard_normal((M, n, d)).astype(np.float32)
    ym = rng.standard_normal((M, n, d)).astype(np.float32)
    x = np.zeros((W, M, n, d), np.float32)
    y = np.zeros((W, M, n, d), np.float32)
    x[0], y[-1] = xm, ym
    sh = comm.sharding(P(pp.AXIS, None, None, None))
    return xm, ym, jax.device_put(x, sh), jax.device_put(y, sh)


# ---------------------------------------------------------------------------
# the schedule table (host rung)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("world,M,V", [
    (2, 2, 1), (2, 4, 1), (4, 4, 1), (4, 8, 1), (8, 16, 1),
    (2, 4, 2), (4, 8, 2), (3, 6, 2),
])
def test_schedule_table_covers_every_unit(world, M, V):
    """Every (microbatch, chunk) forwards AND backwards exactly once on
    its owning rank, dependencies are respected tick by tick, and no
    slot is read before it was written."""
    tab = pp.schedule_table(world, M, V)
    N = world * V
    f_done, b_done = {}, {}
    for t in range(tab.steps):
        for r in range(world):
            if tab.f_mb[t, r] >= 0:
                m, c = int(tab.f_mb[t, r]), int(tab.f_chunk[t, r])
                sig = c * world + r
                assert (m, sig) not in f_done
                if sig > 0:   # upstream stage forwarded >= 2 ticks ago
                    assert f_done[(m, sig - 1)] <= t - 1
                f_done[(m, sig)] = t
            if tab.b_mb[t, r] >= 0:
                m, c = int(tab.b_mb[t, r]), int(tab.b_chunk[t, r])
                sig = c * world + r
                assert (m, sig) not in b_done
                assert f_done[(m, sig)] < t        # own forward first
                if sig < N - 1:
                    assert b_done[(m, sig + 1)] <= t - 1
                b_done[(m, sig)] = t
    assert len(f_done) == len(b_done) == M * N


@pytest.mark.parametrize("world,M", [(2, 4), (4, 8), (8, 16), (8, 24)])
def test_schedule_stash_is_o_world(world, M):
    """THE 1F1B memory claim: the plain schedule's stash never exceeds
    ``world`` slots no matter how many microbatches run — vs GPipe's
    ``M`` stashed activations."""
    tab = pp.schedule_table(world, M, 1)
    assert tab.stash_slots <= world
    assert tab.max_live <= world
    assert tab.bubble_fraction <= pp.gpipe_bubble_fraction(world, M) + 1e-9


def test_schedule_interleave_cuts_bubble():
    """Virtual stages trade stash for fill time: at the same (world, M)
    the interleaved schedule's bubble fraction drops below the plain
    one's."""
    plain = pp.schedule_table(4, 8, 1)
    inter = pp.schedule_table(4, 8, 2)
    assert inter.bubble_fraction < plain.bubble_fraction
    # the stash grows, but stays O(world * V), never O(M * V)
    assert inter.stash_slots <= 2 * 4 * 2


def test_degenerate_geometry_raises():
    """M < world cannot be covered by the 1F1B masks — the regression
    for the old demo's silent-garbage mode: loud ValueError, and the
    "auto" arbiter degrades to the GPipe baseline instead."""
    with pytest.raises(ValueError, match="n_micro >= world"):
        pp.schedule_table(4, 2, 1)
    comm = _sub_comm(4)
    with pytest.raises(ValueError, match="n_micro >= world"):
        pp.build_pp_train_step(comm, 2, 8, schedule="1f1b")
    step = pp.build_pp_train_step(comm, 2, 8, schedule=None)
    assert step.schedule == "gpipe"
    assert step.decision_source == "degenerate"


def test_schedule_register_validation():
    with pytest.raises(ValueError, match="pp_schedule"):
        pp.set_schedule("bogus")
    with pytest.raises(ValueError, match="pp_interleave"):
        pp.set_interleave(0)


def test_resolve_pp_schedule_counted():
    """The arbitration is attributable: every resolution lands in
    accl_sched_plan_total{op="pipeline"} with its source."""
    decision, source = pp.resolve_pp_schedule("1f1b", 4, 8, 1 << 20)
    assert (decision, source) == ("1f1b", "register")
    decision, source = pp.resolve_pp_schedule(None, 4, 8, 1 << 20)
    assert source in ("cost_model", "register")
    snap = str(metrics.snapshot())
    assert 'op="pipeline"' in snap


# ---------------------------------------------------------------------------
# train-step parity (emulator rung) — the bit-tolerance suite
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("world,M,V", [
    (2, 4, 1), (4, 8, 1), (2, 4, 2),
])
def test_pp_train_parity_and_oracle(world, M, V, rng):
    """Loss-trajectory parity at worlds {2, 4}: the 1F1B masked scan
    (manual stash-and-recompute backward) and the GPipe oracle
    (autodiff through the cond-skipped scan) trace the same losses and
    parameters, and the first step's loss matches the float64 host
    reference."""
    comm = _sub_comm(world)
    d, n = 8, 3
    gp = pp.init_stage_params(jax.random.PRNGKey(0), comm, d, interleave=V)
    xm, ym, xg, yg = _pp_io(comm, M, n, d, rng)
    host = pp.PPStageParams(np.asarray(gp.w), np.asarray(gp.b))
    ref = pp.reference_train_loss(host, xm, ym)
    p1 = pp.shard_stage_params(gp, comm)
    pg = pp.shard_stage_params(gp, comm)
    step1 = pp.build_pp_train_step(comm, M, d, lr=1e-2, schedule="1f1b",
                                   interleave=V)
    stepg = pp.build_pp_train_step(comm, M, d, lr=1e-2, schedule="gpipe",
                                   interleave=V)
    assert step1.schedule == "1f1b" and stepg.schedule == "gpipe"
    # plain: THE O(world) bound; interleaved trades stash for bubble
    # (<= 2 * world * V, still never the O(M * V) GPipe slab)
    assert step1.stash_slots <= (world if V == 1 else 2 * world * V)
    losses = []
    for i in range(3):
        p1, l1 = step1(p1, xg, yg)
        pg, lg = stepg(pg, xg, yg)
        if i == 0:
            np.testing.assert_allclose(float(l1), ref, rtol=1e-4)
        np.testing.assert_allclose(float(l1), float(lg), rtol=1e-3)
        losses.append(float(l1))
    np.testing.assert_allclose(np.asarray(p1.w), np.asarray(pg.w),
                               rtol=5e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(p1.b), np.asarray(pg.b),
                               rtol=5e-3, atol=1e-5)
    assert losses[-1] < losses[0]            # it actually trains


def test_pp_1f1b_gradients_match_host_autodiff(rng):
    """The manual 1F1B backward IS the gradient: one lr=1 step's
    parameter delta matches jax.grad of the host model to float32
    resolution (the schedule cannot hide a scaling bug behind
    trajectory similarity)."""
    world, M, V = 4, 8, 1
    comm = _sub_comm(world)
    d, n = 8, 3
    gp = pp.init_stage_params(jax.random.PRNGKey(0), comm, d)
    xm, ym, xg, yg = _pp_io(comm, M, n, d, rng)

    def host_loss(wb):
        w, b = wb
        h = jnp.asarray(xm)
        for c in range(V):
            for r in range(world):
                h = jax.nn.relu(h @ w[r, c] + b[r, c])
        return jnp.mean(jnp.mean((h - jnp.asarray(ym)) ** 2, axis=(1, 2)))

    gw_ref, gb_ref = jax.grad(host_loss)(
        (jnp.asarray(np.asarray(gp.w), jnp.float64),
         jnp.asarray(np.asarray(gp.b), jnp.float64)))
    step = pp.build_pp_train_step(comm, M, d, lr=1.0, schedule="1f1b")
    p2, _ = step(pp.shard_stage_params(gp, comm), xg, yg)
    np.testing.assert_allclose(np.asarray(gp.w) - np.asarray(p2.w),
                               np.asarray(gw_ref), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gp.b) - np.asarray(p2.b),
                               np.asarray(gb_ref), rtol=1e-4, atol=1e-6)


def test_pp_stash_shape_is_traced_o_world(rng):
    """The O(world) claim on TRACED buffer shapes: the 1F1B program's
    scan carries a literal (world, n, d) stash — (n, d) are chosen so
    the shape string is unambiguous against the (M, n, d) input slabs
    (M is 3x world here)."""
    world, M, n, d = 2, 6, 5, 16
    comm = _sub_comm(world)
    step1 = pp.build_pp_train_step(comm, M, d, schedule="1f1b")
    assert step1.stash_slots == world
    assert step1.table.stash_slots == world
    gp = pp.init_stage_params(jax.random.PRNGKey(0), comm, d)
    params = pp.shard_stage_params(gp, comm)
    rng2 = np.random.default_rng(0)
    _, _, xg, yg = _pp_io(comm, M, n, d, rng2)
    # the traced program: the activation stash aval is (world, n, d)
    jaxpr = str(jax.make_jaxpr(
        lambda p, x, y: step1(p, x, y))(params, xg, yg))
    assert f"f32[{world},{n},{d}]" in jaxpr       # THE stash buffer
    # and the schedule's grad-landing buffer stays O(world) too
    assert step1.table.grad_slots <= world


# ---------------------------------------------------------------------------
# the relay op (fallback path on this rung; kernel under interpret/AOT)
# ---------------------------------------------------------------------------


def test_relay_matches_ppermute_reference(accl, rng):
    comm = accl.global_comm()
    W = comm.world_size
    n, d = 4, 8
    f = rng.standard_normal((W, n, d)).astype(np.float32)
    b = rng.standard_normal((W, n, d)).astype(np.float32)
    from accl_tpu.parallel import algorithms
    from accl_tpu import Algorithm
    prog = algorithms.build_pipeline_relay(comm, Algorithm.XLA)
    sh = comm.sharding(P(pp.AXIS, None, None))
    fo, bo = prog(jax.device_put(f, sh), jax.device_put(b, sh))
    np.testing.assert_allclose(np.asarray(fo), np.roll(f, 1, axis=0),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(bo), np.roll(b, -1, axis=0),
                               rtol=1e-6)


def test_relay_vjp_parity(accl, rng):
    """The relay's custom VJP is the channel-swapped relay: gradients
    through pp_relay match gradients through the plain ppermute pair."""
    comm = accl.global_comm()
    W = comm.world_size
    n, d = 4, 8
    f = rng.standard_normal((W, n, d)).astype(np.float32)
    b = rng.standard_normal((W, n, d)).astype(np.float32)
    sh = comm.sharding(P(pp.AXIS, None, None))
    fg, bg = jax.device_put(f, sh), jax.device_put(b, sh)
    from jax import shard_map
    from jax import lax

    fwd_perm = [(i, (i + 1) % W) for i in range(W)]
    bwd_perm = [(i, (i - 1) % W) for i in range(W)]

    def loss_relay(f, b):
        fo, bo = relay.pp_relay(f[0], b[0], pp.AXIS, (pp.AXIS,), None)
        return jnp.sum(fo * fo) + jnp.sum(bo * bo * 2.0)

    def loss_ref(f, b):
        fo = lax.ppermute(f[0], pp.AXIS, fwd_perm)
        bo = lax.ppermute(b[0], pp.AXIS, bwd_perm)
        return jnp.sum(fo * fo) + jnp.sum(bo * bo * 2.0)

    def grads(loss):
        def local(f, b):
            gf, gb = jax.grad(loss, argnums=(0, 1))(f, b)
            return gf, gb
        prog = jax.jit(shard_map(
            local, mesh=comm.mesh, in_specs=(P(pp.AXIS), P(pp.AXIS)),
            out_specs=(P(pp.AXIS), P(pp.AXIS)), check_vma=False))
        return prog(fg, bg)

    gf1, gb1 = grads(loss_relay)
    gf2, gb2 = grads(loss_ref)
    np.testing.assert_allclose(np.asarray(gf1), np.asarray(gf2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gb1), np.asarray(gb2), rtol=1e-6)


def test_relay_engage_reasons():
    """The engage-reason honesty vocabulary: requested-off is "off"
    (never counted), world=1 is "geometry", and this rung's kernel
    unavailability is attributable."""
    assert relay.relay_engage_reason(4, 8, np.float32, 4,
                                     overlap=False) == "off"
    assert relay.relay_engage_reason(4, 8, np.float32, 1) == "geometry"
    r = relay.relay_engage_reason(4, 8, np.float32, 4, overlap=True)
    assert r in (None, "no_interpret")          # rung-dependent
    # plan geometry: segments cover the payload, slots stay bounded
    plan = relay.pp_plan(64, 256, np.float32, 4)
    assert plan is not None
    assert plan["C"] * plan["seg_elems"] >= 64 * 256
    assert plan["vmem_bytes"] <= relay._VMEM_BUDGET


def test_relay_fallback_counted(accl, rng):
    """A relay decline (not requested-off) lands in
    accl_cmatmul_fallback_total{op="pp_relay"} and the dispatch-path
    counter records which path ran."""
    comm = accl.global_comm()
    W = comm.world_size
    sh = comm.sharding(P(pp.AXIS, None, None))
    f = jax.device_put(rng.standard_normal((W, 2, 8)).astype(np.float32),
                       sh)
    from accl_tpu.parallel import algorithms
    from accl_tpu import Algorithm
    prog = algorithms.build_pipeline_relay(comm, Algorithm.PALLAS)
    try:
        jax.block_until_ready(prog(f, f))
        ran = True
    except Exception:
        ran = False
    snap = str(metrics.snapshot())
    if relay.relay_engages(2, 8, np.float32, W, overlap=True):
        assert ran
        assert 'accl_pp_relay_total{path="fused"}' in snap
    else:
        assert 'op="pp_relay"' in snap          # the counted decline
        assert 'accl_pp_relay_total{path="ppermute"}' in snap


# ---------------------------------------------------------------------------
# the composed (pp, dp, tp) step (emulator rung)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ppsz,dp,tp", [(2, 2, 1), (2, 1, 2)])
def test_composed_step_parity(ppsz, dp, tp, rng):
    """The composed transformer step: 1F1B and GPipe schedules trace
    the same loss trajectory and parameters on pp x dp and pp x tp
    meshes (requested-baseline datapath on this rung — the schedule is
    what's under test; the fused arm is AOT-pinned below)."""
    mesh = pp.make_pp_mesh(jax.devices()[:ppsz * dp * tp], ppsz, dp, tp)
    d, h, heads, M, b = 8, 16, 2, 4, 4
    params = pp.init_pp_transformer(jax.random.PRNGKey(0), mesh, d, h,
                                    heads)
    B = dp * b
    sh = NamedSharding(mesh, P(None, "dp", None))
    x = jax.device_put(
        rng.standard_normal((M, B, d)).astype(np.float32) * .3, sh)
    y = jax.device_put(
        rng.standard_normal((M, B, d)).astype(np.float32) * .3, sh)
    step1 = pp.build_pp_transformer_train_step(
        mesh, d, h, heads, M, lr=1e-2, schedule="1f1b", overlap=False)
    stepg = pp.build_pp_transformer_train_step(
        mesh, d, h, heads, M, lr=1e-2, schedule="gpipe", overlap=False)
    p1 = pg = params
    losses = []
    for _ in range(3):
        p1, l1 = step1(p1, x, y)
        pg, lg = stepg(pg, x, y)
        np.testing.assert_allclose(float(l1), float(lg), rtol=2e-3)
        losses.append(float(l1))
    assert step1.schedule == "1f1b"           # requested baseline runs
    assert step1.engage_reason == "off"       # ... uncounted
    assert step1.stash_slots <= ppsz
    for a, bb in zip(jax.tree_util.tree_leaves(p1),
                     jax.tree_util.tree_leaves(pg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=5e-3, atol=1e-5)
    assert losses[-1] < losses[0]


def test_composed_commit_honesty(rng):
    """A declining per-stage plan (overlap=None resolves fused on this
    rung and the kernels cannot run) demotes the WHOLE step to the
    GPipe baseline, counted under
    accl_cmatmul_fallback_total{op="pp_pipeline"} — never a degraded
    unfused rendition presented as 1F1B."""
    if relay.relay_engages(4, 8, np.float32, 2, overlap=True):
        pytest.skip("fused relay runs on this rung — no decline to test")
    mesh = pp.make_pp_mesh(jax.devices()[:4], 2, 2, 1)
    d, h, heads, M, b = 8, 16, 2, 4, 4
    params = pp.init_pp_transformer(jax.random.PRNGKey(0), mesh, d, h,
                                    heads)
    sh = NamedSharding(mesh, P(None, "dp", None))
    x = jax.device_put(
        rng.standard_normal((M, 2 * b, d)).astype(np.float32) * .3, sh)
    step = pp.build_pp_transformer_train_step(
        mesh, d, h, heads, M, schedule="1f1b", overlap=None)
    step(params, x, x)
    assert step.schedule == "gpipe"
    assert step.fused is False
    assert step.engage_reason == "no_interpret"
    assert step.decision_source == "fallback"
    snap = str(metrics.snapshot())
    assert 'op="pp_pipeline"' in snap


# ---------------------------------------------------------------------------
# interpret rung: the relay kernel under the race detector
# ---------------------------------------------------------------------------


def test_relay_kernel_race_free(accl, rng, monkeypatch):
    """The double-buffer + credit protocol under the interpret-mode
    race detector (grants == gates; every semaphore drains to zero)."""
    from jax.experimental.pallas import tpu as pltpu
    from accl_tpu.parallel import pallas_ring

    monkeypatch.setattr(
        pallas_ring, "_interpret_params",
        lambda: pltpu.InterpretParams(detect_races=True))
    comm = accl.global_comm()
    W = comm.world_size
    n, d = 8, 640       # multiple segments: the credit chain is real
    f = rng.standard_normal((W, n, d)).astype(np.float32)
    b = rng.standard_normal((W, n, d)).astype(np.float32)
    from accl_tpu.parallel import algorithms
    from accl_tpu import Algorithm
    prog = algorithms.build_pipeline_relay(comm, Algorithm.PALLAS)
    sh = comm.sharding(P(pp.AXIS, None, None))
    fo, bo = prog(jax.device_put(f, sh), jax.device_put(b, sh))
    np.testing.assert_allclose(np.asarray(fo), np.roll(f, 1, axis=0),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(bo), np.roll(b, -1, axis=0),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# AOT v5e:2x4 pins: the relay kernel + the composed fused step
# ---------------------------------------------------------------------------

WORLD = 8


@pytest.fixture(scope="module")
def tpu_comm():
    from conftest import aot_topology_devices
    devices = aot_topology_devices("v5e:2x4")
    assert len(devices) == WORLD
    return Communicator(devices)


def test_relay_kernel_lowers_multihost(tpu_comm):
    """The relay kernel AOT-compiles for the 2-host v5e topology: Mosaic
    accepted the double-buffered staging, the counter-direction remote
    DMAs and the credit semaphores for hardware."""
    from conftest import assert_aot_lowered
    from accl_tpu.parallel import algorithms, pallas_ring
    from accl_tpu import Algorithm

    n, d = 128, 512
    plan = relay.pp_plan(n, d, jnp.float32, WORLD)
    assert plan is not None and plan["C"] >= 1
    fn = algorithms.build_pipeline_relay(tpu_comm, Algorithm.PALLAS)
    sh = tpu_comm.sharding()
    arg = jax.ShapeDtypeStruct((WORLD, n, d), jnp.float32, sharding=sh)
    with jax.enable_x64(False), pallas_ring.aot_lowering():
        compiled = fn.lower(arg, arg).compile()
    assert_aot_lowered(compiled, 1)


@pytest.mark.slow
def test_composed_fused_step_lowers_multihost():
    """The composed (pp, dp, tp) 1F1B train step with the fused
    datapath forced AOT-compiles for v5e:2x4 — flash fwd/bwd, the
    agmm/mmrs MLP family and the relay kernel in ONE program, with
    trace-level kernel counts pinned (>= 4 Mosaic kernels: relay +
    flash + agmm forward + mmrs/wgrad backward)."""
    from conftest import aot_topology_devices, assert_aot_lowered
    from accl_tpu.parallel import pallas_ring

    devices = aot_topology_devices("v5e:2x4")
    mesh = pp.make_pp_mesh(devices, 2, 2, 2)
    d, h, heads, M, b = 256, 1024, 4, 4, 128
    with jax.enable_x64(False), pallas_ring.aot_lowering():
        step = pp.build_pp_transformer_train_step(
            mesh, d, h, heads, M, schedule="1f1b", overlap=True)
        specs = pp.pp_transformer_specs()
        from accl_tpu.models import zero
        dtp, n_attn = zero._attn_sizes(d, 2)
        n_attn_pad = n_attn + (-n_attn) % 2
        params = pp.PPTransformerParams(
            attn=jax.ShapeDtypeStruct(
                (2, 2, n_attn_pad), jnp.float32,
                sharding=NamedSharding(mesh, specs.attn)),
            w1t=jax.ShapeDtypeStruct(
                (2, h, d), jnp.float32,
                sharding=NamedSharding(mesh, specs.w1t)),
            w2t=jax.ShapeDtypeStruct(
                (2, d, h), jnp.float32,
                sharding=NamedSharding(mesh, specs.w2t)),
        )
        xs = jax.ShapeDtypeStruct(
            (M, 2 * b, d), jnp.float32,
            sharding=NamedSharding(mesh, P(None, "dp", None)))
        # the fused datapath must ENGAGE for this geometry under the
        # AOT force-compile context — the pin is meaningless otherwise
        reason = pp.pp_transformer_engage_reason(
            d, h, b, 2, 2, 2, overlap=True)
        assert reason is None, f"fused datapath declined: {reason}"
        compiled = step.lower(params, xs, xs).compile()
    assert_aot_lowered(compiled, 4)
    assert step.schedule == "1f1b" and step.fused
