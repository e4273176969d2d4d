"""AOT compiles of the main path's kernels at the sizes ``chip_smoke.py``
runs them, for a described (not attached) ``v5e:2x2`` host.

Interpret mode cannot see what only the TPU compiler refuses: a block
that overflows VMEM, a slice not aligned to the tiling, a program that
does not fit HBM. Each test compiles one kernel family at its real size
and asserts that the compiled module holds the Mosaic kernel
(``tpu_custom_call`` — not an interpret-mode callback, not a lax
reference) and that its buffer plan fits the chip's 16 GiB.

The topology is described in a module-scoped fixture, never at import:
describing it loads libtpu, which one process at a time may hold
(``on-chip-measurement`` guide, section 2). All such compiles live in
this one file so one pytest-xdist worker takes them all.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from accl_tpu.communicator import Communicator
from accl_tpu.constants import dataType, reduceFunction
from accl_tpu.ops import compression, flash, reduce_ops
from accl_tpu.parallel import algorithms, pallas_ring
from accl_tpu.config import Algorithm
from conftest import AOT_HBM_BYTES, MOSAIC_CALL

MIB = 1 << 20
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def comm4(topo):
    devices = list(topo.devices)
    assert len(devices) == 4
    return Communicator(devices)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; return its module text
    after checking the kernel is there and the plan fits HBM."""
    with jax.enable_x64(False), pallas_ring.aot_lowering():
        compiled = jax.jit(fn).lower(*args).compile()
    txt = compiled.as_text()
    assert MOSAIC_CALL.search(txt), "no Mosaic kernel in the module"
    ma = compiled.memory_analysis()
    plan = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert plan < AOT_HBM_BYTES, f"buffer plan {plan} exceeds HBM"
    return txt


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("func", [reduceFunction.SUM, reduceFunction.MAX])
def test_combine_64mib_compiles(one_chip, func):
    """The reduce_ops lane at the bucket size, as the session's (1, n)
    buffer reaches it: the wide 512x512 block geometry."""
    x = _shape(one_chip, (1, 64 * MIB // 4), F32)
    _compile(lambda a, b: reduce_ops.pallas_combine(a, b, func), x, x)


def test_cast_roundtrip_64mib_compiles(one_chip):
    x = _shape(one_chip, (1, 64 * MIB // 4), F32)
    txt = _compile(lambda v: compression.pallas_cast(
        compression.pallas_cast(v, BF16), F32), x)
    assert len(MOSAIC_CALL.findall(txt)) == 2


def test_stochastic_compress_64mib_compiles(one_chip):
    """The TPU-only stochastic-rounding lane (PRNG in the kernel)."""
    x = _shape(one_chip, (1, 64 * MIB // 4), F32)
    seed = _shape(one_chip, (), I32)
    _compile(lambda v, s: compression.pallas_compress_stochastic(
        v, BF16, seed=s), x, seed)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_bwd_s4096_compiles(one_chip, causal):
    """Forward + fused backward at H=32, S=4096, d=128 bf16: exactly the
    forward kernel and ONE fused backward kernel."""
    qkv = _shape(one_chip, (32, 4096, 128), BF16)

    def loss(q, k, v):
        out = flash.flash_attention(q, k, v, causal=causal,
                                    bwd_mode="fused")
        return out.astype(F32).sum()

    txt = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                   qkv, qkv, qkv)
    assert len(MOSAIC_CALL.findall(txt)) == 2


def test_paged_flash_decode_compiles(one_chip):
    """32 slots x 4096-token contexts in 128-token pages, 8 kv heads of
    d=128 under 32 query heads (GQA 4:1), bf16 pools."""
    B, H, hkv, d, page, ctx = 32, 32, 8, 128, 128, 4096
    pages_max = ctx // page
    q = _shape(one_chip, (B, H, d), BF16)
    kv = _shape(one_chip, (hkv, B * pages_max, page, d), BF16)
    tables = _shape(one_chip, (B, pages_max), I32)
    lens = _shape(one_chip, (B,), I32)
    with pallas_ring.aot_lowering():
        plan, reason = flash.decode_plan(B, H, hkv, d, page, pages_max, 2,
                                         kv_itemsize=2)
    assert plan is not None, reason
    _compile(flash.flash_decode, q, kv, kv, tables, lens)


def test_bidirectional_chunked_allreduce_64mib_compiles(comm4):
    """The Pallas ring allreduce at 64 MiB per rank over the four chips
    of one v5e host: past the VMEM threshold it runs the segmented
    (chunked) bidirectional kernels — reduce-scatter and all-gather."""
    fn = pallas_ring.build_pallas_ring_allreduce(
        comm4, reduceFunction.SUM, dataType.float32, bidirectional=True)
    x = _shape(comm4.sharding(), (4, 64 * MIB // 4), F32)
    txt = _compile(fn, x)
    assert len(MOSAIC_CALL.findall(txt)) >= 2


@pytest.mark.parametrize("op", ["agmm", "mmrs"])
def test_collective_matmul_zero3_width_compiles(comm4, op):
    """all_gather_matmul / matmul_reduce_scatter at a ZeRO-3 MLP width
    (d=4096, ffn=16384, 4096 tokens) over the four chips, bf16."""
    P, m, k, n = 4, 4096, 4096, 16384
    if op == "agmm":
        fn = algorithms.build_allgather_matmul(comm4, Algorithm.PALLAS)
        x = _shape(comm4.sharding(), (P, m // P, k), BF16)
        w = _shape(comm4.sharding(), (P, k, n // P), BF16)
    else:
        fn = algorithms.build_matmul_reduce_scatter(comm4, Algorithm.PALLAS)
        x = _shape(comm4.sharding(), (P, m, n // P), BF16)
        w = _shape(comm4.sharding(), (P, n // P, k), BF16)
    _compile(fn, x, w)

