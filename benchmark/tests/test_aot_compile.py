"""AOT compiles of ``grad_sync.4chip``'s programs at 1 GiB per rank for a
described (not attached) ``v5e:2x2`` host: the programs the session
builds for AUTO, through its own per-op specs.

The topology is described in a module-scoped fixture, never at import
(``on-chip-measurement`` guide, section 2).
"""
import jax
import pytest

from benchmark import ops, spec
from benchmark.generators import rounds

GIB = 1 << 30
HBM = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def acc(topo):
    import accl_tpu

    session = accl_tpu.ACCL(devices=list(topo.devices))
    yield session
    session.deinit()


@pytest.mark.parametrize("op", ["allreduce", "reduce_scatter", "allgather"])
def test_grad_sync_program_at_1gib_compiles(acc, op):
    from accl_tpu import dataType, reduceFunction
    from accl_tpu.parallel import pallas_ring

    cell = spec.resolve_cell("grad_sync.4chip")
    assert max(rounds.sizes(cell.traffic)) == GIB
    g = ops.geometry(ops.load(op), GIB, cell.traffic, 4, 4)
    comm = acc.global_comm()
    f32, SUM = dataType.float32, reduceFunction.SUM
    if op == "allreduce":
        _, build = acc._spec_allreduce(comm, g.count, f32, SUM, None, None)
    elif op == "reduce_scatter":
        _, build = acc._spec_reduce_scatter(comm, g.count, f32, SUM, None,
                                            None)
    else:
        _, build = acc._spec_allgather(comm, g.count, f32, None, None)
    x = jax.ShapeDtypeStruct((4, g.in_elems), jax.numpy.float32,
                             sharding=comm.sharding())
    with pallas_ring.aot_lowering():
        compiled = jax.jit(build()).lower(x).compile()
    out = compiled.output_shardings
    assert out is not None
    ma = compiled.memory_analysis()
    plan = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert ma.output_size_in_bytes == g.out_elems * 4
    assert plan < HBM, f"buffer plan {plan} exceeds HBM"
