"""Device-initiated collective tests (accl_hls.h PL-kernel API analog):
collectives invoked inside jitted compute, the vadd_put example, and the
flagship dp x tp MLP training step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from accl_tpu import Communicator, device_api as dapi, reduceFunction
from accl_tpu.models import mlp, vadd

WORLD = 8
AXIS = Communicator.AXIS


def _smap(comm, fn, out_specs=P(AXIS)):
    return jax.jit(shard_map(fn, mesh=comm.mesh, in_specs=P(AXIS),
                             out_specs=out_specs, check_vma=False))


def _sharded(comm, data):
    return jax.device_put(data, comm.sharding())


def test_in_kernel_allreduce(accl, rng):
    comm = accl.global_comm()
    data = rng.standard_normal((WORLD, 64)).astype(np.float32)

    def kernel(x):
        y = x * 2.0                      # compute stage
        return dapi.allreduce(y)         # fused collective

    out = np.asarray(_smap(comm, kernel)(_sharded(comm, data)))
    expect = (data * 2).sum(0)
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], expect, rtol=1e-5)


def test_in_kernel_bcast_and_rank(accl, rng):
    comm = accl.global_comm()
    data = rng.standard_normal((WORLD, 16)).astype(np.float32)

    def kernel(x):
        r = dapi.rank()
        y = x + r.astype(jnp.float32)    # rank-dependent compute
        return dapi.bcast(y, root=3)

    out = np.asarray(_smap(comm, kernel)(_sharded(comm, data)))
    expect = data[3] + 3.0
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], expect, rtol=1e-6)


def test_in_kernel_reduce_scatter_allgather_roundtrip(accl, rng):
    comm = accl.global_comm()
    n = WORLD * 32
    data = rng.standard_normal((WORLD, n)).astype(np.float32)

    def kernel(x):
        shard = dapi.reduce_scatter(x[0])[None, :]
        full = dapi.all_gather(shard[0])[None, :]
        return full

    out = np.asarray(_smap(comm, kernel)(_sharded(comm, data)))
    expect = data.sum(0)
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], expect, rtol=1e-4, atol=1e-5)


def test_in_kernel_alltoall(accl, rng):
    comm = accl.global_comm()
    count = 4
    data = rng.standard_normal((WORLD, WORLD * count)).astype(np.float32)

    def kernel(x):
        return dapi.all_to_all(x[0])[None, :]

    out = np.asarray(_smap(comm, kernel)(_sharded(comm, data)))
    for r in range(WORLD):
        for q in range(WORLD):
            np.testing.assert_array_equal(
                out[r, q * count:(q + 1) * count],
                data[q, r * count:(r + 1) * count])


def test_vadd_put_example(accl, rng):
    """vadd_put.cpp semantics: out[r] = in[r-1] + 1 (ring put, no host)."""
    comm = accl.global_comm()
    data = rng.standard_normal((WORLD, 50)).astype(np.float32)
    out = np.asarray(vadd.run_vadd_put(comm, data, add=1.0))
    for r in range(WORLD):
        np.testing.assert_allclose(out[r], data[(r - 1) % WORLD] + 1.0,
                                   rtol=1e-6)


def test_in_kernel_barrier_and_world(accl):
    comm = accl.global_comm()

    def kernel(x):
        tok = dapi.barrier()
        return x + tok.astype(x.dtype)  # tok == world everywhere

    data = np.zeros((WORLD, 4), np.float32)
    out = np.asarray(_smap(comm, kernel)(_sharded(comm, data)))
    np.testing.assert_array_equal(out, np.full((WORLD, 4), WORLD, np.float32))


# ---- flagship model: dp x tp MLP ----------------------------------------

def test_mlp_forward_matches_single_device(rng):
    d, h, b = 16, 32, 8
    params = mlp.init_params(jax.random.PRNGKey(0), d, h)
    x = rng.standard_normal((b, d)).astype(np.float32)
    # reference: plain single-device forward
    ref = np.asarray(
        jnp.dot(jax.nn.gelu(jnp.dot(jnp.asarray(x), params.w1) + params.b1),
                params.w2) + params.b2
    )
    mesh = mlp.make_mesh(jax.devices()[:8], dp=2, tp=4)
    p_sh = mlp.shard_params(params, mesh)
    fwd = mlp.make_forward(mesh)
    x_sh = jax.device_put(x, jax.NamedSharding(mesh, P(mlp.DP_AXIS, None)))
    out = np.asarray(fwd(p_sh, x_sh))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_mlp_train_step_decreases_loss(rng):
    d, h, b = 16, 32, 16
    mesh = mlp.make_mesh(jax.devices()[:8], dp=2, tp=4)
    params = mlp.shard_params(
        mlp.init_params(jax.random.PRNGKey(1), d, h), mesh)
    step = mlp.make_train_step(mesh, lr=5e-2)
    x = jax.device_put(rng.standard_normal((b, d)).astype(np.float32),
                       jax.NamedSharding(mesh, P(mlp.DP_AXIS, None)))
    t = jax.device_put(rng.standard_normal((b, d)).astype(np.float32),
                       jax.NamedSharding(mesh, P(mlp.DP_AXIS, None)))
    losses = []
    for _ in range(30):
        params, loss = step(params, x, t)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[:3] + losses[-3:]


def test_in_kernel_scatter_gather(accl, rng):
    """ACCLCommand::scatter / ::gather analogs inside jitted compute."""
    comm = accl.global_comm()
    w = comm.world_size
    x = rng.standard_normal((w, 4 * w)).astype(np.float32)

    def kernel(v):
        mine = dapi.scatter(v, root=2)         # (1, 4) chunk per rank
        back = dapi.gather(mine, root=2)       # (1, 4*w) at root
        return back

    prog = _smap(comm, kernel)
    out = np.asarray(prog(_sharded(comm, x)))
    np.testing.assert_allclose(out[2], x[2], rtol=1e-5)
    assert np.all(out[0] == 0)                 # non-root zeros
