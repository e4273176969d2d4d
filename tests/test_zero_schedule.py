"""Multi-host AOT lowering proof for the layerwise ZeRO/FSDP step.

Mirrors ``test_cmatmul_schedule.py``: the flagship train step — flash
attention + per-layer agmm parameter gathers (attention AND MLP, round
20) + their dual mmrs/wgrad backward kernels — AOT-compiles against a
real ``v5e:2x4`` TPU topology on a (dp=4, tp=2) mesh. A successful
compile proves Mosaic accepted every fused kernel the layerwise
schedule traces and XLA scheduled the composed program for a 2-host
mesh; the kernel COUNT pins the acceptance bar (>= 12 collective-matmul
kernels per transformer layer: 4 forward agmm gathers — Wqkvᵀ, Woᵀ,
W1ᵀ, W2ᵀ — their 4 dual mmrs gradient reductions and 4 fused
gathered-wgrad kernels; no unfused parameter collective survives —
plus the per-layer flash fwd/bwd pair)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from accl_tpu.models import zero
from accl_tpu.ops import collective_matmul as cm
from accl_tpu.parallel import pallas_ring
from conftest import assert_aot_lowered, aot_topology_devices

WORLD, DP, TP = 8, 4, 2
D, HID, HEADS, B_RANK = 256, 1024, 8, 128


@pytest.fixture(scope="module")
def fsdp_mesh():
    devices = aot_topology_devices("v5e:2x4")
    assert len(devices) == WORLD
    return zero.make_mesh(devices, DP, TP)


def _state_structs(mesh, n_layers):
    specs = zero.fsdp_param_specs(n_layers)
    _, _, q_rows_pad = zero._attn_travel_sizes(D, TP, DP)

    def leaf(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    p = zero.FSDPParams(
        wqkvt=tuple(leaf((TP * q_rows_pad, D), s) for s in specs.wqkvt),
        wot=tuple(leaf((D, D), s) for s in specs.wot),
        w1t=tuple(leaf((HID, D), s) for s in specs.w1t),
        w2t=tuple(leaf((D, HID), s) for s in specs.w2t),
    )
    t = jax.ShapeDtypeStruct((), jnp.int32,
                             sharding=NamedSharding(mesh, P()))
    return zero.ZeroFSDPState(p=p, m=p, v=p, t=t)


def _x_struct(mesh):
    return jax.ShapeDtypeStruct(
        (DP * B_RANK, D), jnp.float32,
        sharding=NamedSharding(mesh, P(zero.DP_AXIS, None)))


def _compile(mesh, n_layers, **kw):
    with jax.enable_x64(False), pallas_ring.aot_lowering():
        step = zero.build_zero_fsdp_train_step(
            mesh, n_layers, D, HID, HEADS, overlap=True, **kw)
        st = _state_structs(mesh, n_layers)
        xs = _x_struct(mesh)
        return step.lower(st, xs, xs).compile()


def test_fsdp_plans_resident():
    """Geometry pin: all four per-layer gather plans — attention and
    MLP travel shards — resolve VMEM-resident at the flagship shapes
    (a padding/budget change is a visible diff, not a silicon
    surprise)."""
    h_tp = HID // TP
    dtp, _, qrp = zero._attn_travel_sizes(D, TP, DP)
    for m, k in ((h_tp // DP, D), (D // DP, h_tp),
                 (qrp // DP, D), (D // DP, dtp)):
        p = cm.agmm_plan(m, k, B_RANK, DP, jnp.float32, True)
        assert p is not None and p["mode"] == "resident"
    with pallas_ring.aot_lowering():
        # kernels-available is forced, as at compile: the whole engage
        # resolution (plans + registers) must say yes for these shapes
        assert zero.fsdp_engages(D, HID, B_RANK, DP, TP, overlap=True)
        assert zero.fsdp_attn_engages(D, B_RANK, DP, TP, overlap=True)


def test_fsdp_train_step_lowers_multihost(fsdp_mesh):
    """The flagship workload end to end: TWO transformer layers of
    (flash fwd/bwd + 12 collective-matmul kernels each — the attention
    projections on the agmm family too, round 20) in ONE jitted
    program lower for the 2-host (dp=4, tp=2) mesh with ZERO unfused
    parameter collectives."""
    L = 2
    compiled = _compile(fsdp_mesh, L)
    # >= 12 cmatmul + 2 flash Mosaic kernels per layer, less one: the
    # first layer's Wqkv gathered-wgrad computes d(input), which the
    # step never differentiates — jax 0.9 drops that leg before lowering
    assert_aot_lowered(compiled, 14 * L - 1)


def test_fsdp_train_step_wire_lowers_multihost(fsdp_mesh):
    """bf16 wire staging lowers: the ring kernels' staged slots at half
    the bytes plus the hp_compression cast lanes (shard casts + the
    bucketized gradient leg)."""
    compiled = _compile(fsdp_mesh, 1, wire_dtype="bf16")
    assert_aot_lowered(compiled, 15)
