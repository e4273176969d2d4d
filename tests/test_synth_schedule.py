"""AOT v5e:2x4 pins for the synthesized multi-axis schedules.

Mirrors ``test_flat_schedule.py``: the multi-axis builders compile
ahead-of-time against a real v5e 2x4 TPU topology, proving (1) the chip
coordinates of the real torus auto-detect as its (y, x) factorization —
no declaration needed on silicon, (2) the plan resolution keeps XLA's
own collective there (ICI's baseline) while explicit MULTIAXIS requests
plan the multi-axis schedule on the detected grid, and
(3) the whole synthesized schedule lowers as ONE program whose
scheduled module runs the per-axis collectives (no flat 8-rank ring in
sight). Compile-only — skips where libtpu cannot provide topology
descriptions, like every *_schedule module."""
import re

import jax
import jax.numpy as jnp
import pytest

from accl_tpu.config import ACCLConfig, Algorithm, TransportBackend
from accl_tpu.communicator import Communicator
from accl_tpu.constants import dataType, operation, reduceFunction
from accl_tpu.parallel import algorithms, synth

WORLD, ROWS, COLS = 8, 2, 4


@pytest.fixture(scope="module")
def tpu_comm():
    from conftest import aot_topology_devices
    devices = aot_topology_devices("v5e:2x4")
    assert len(devices) == WORLD
    return Communicator(devices)


@pytest.fixture(scope="module")
def coords_grid(tpu_comm):
    """(rows, cols) of the described chips read straight off their
    coordinates: cols = the x extent (fastest-varying under snake rank
    order), rows = the y extent. libtpu's naming of "2x4" changed
    between releases (x=4 once, x=2 under libtpu 0.0.34), so the
    expectation comes from the coordinates, not from the name."""
    xs = {d.coords[0] for d in tpu_comm.devices}
    ys = {d.coords[1] for d in tpu_comm.devices}
    assert len(xs) * len(ys) == WORLD and min(len(xs), len(ys)) >= 2
    return len(ys), len(xs)


def _compile_text(fn, comm, *shapes):
    sh = comm.sharding()
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sh)
            for s in shapes]
    return fn.lower(*args).compile().as_text()


def test_v5e_coords_detect_torus(tpu_comm, coords_grid):
    """The real 2x4 slice's chip coords ARE the torus declaration: AUTO
    synthesizes multi-axis schedules on silicon with a default config."""
    cfg = ACCLConfig(transport=TransportBackend.ICI)
    assert synth.torus_shape(tpu_comm, cfg) == coords_grid
    topo = synth.topology_of(tpu_comm, cfg)
    assert topo.axes == coords_grid and topo.multi_axis


def test_v5e_resolution_keeps_xla_explicit_multiaxis_plans(tpu_comm,
                                                           coords_grid):
    """Plan pin on the real topology: AUTO's large-payload allreduce
    keeps XLA's own collective on the detected torus (ICI's window
    baseline, which the ladder's plan does not beat), decided by the
    cost model; an explicit MULTIAXIS request still selects the
    multi-axis schedule, whose plans — sequential and pipelined —
    validate on the detected grid."""
    cfg = ACCLConfig(transport=TransportBackend.ICI)
    got = algorithms.select(operation.allreduce, 8 << 20, tpu_comm, cfg)
    assert got == Algorithm.XLA
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20,
                                       tpu_comm, cfg)
    plan = synth.resolve(operation.allreduce, 8 << 20, tpu_comm, cfg,
                         legacy)
    assert plan.shape == "xla" and plan.source == "cost_model"
    assert plan.topology.axes == coords_grid
    assert algorithms.select(operation.allreduce, 8 << 20, tpu_comm, cfg,
                             requested=Algorithm.MULTIAXIS) \
        == Algorithm.MULTIAXIS
    topo = synth.topology_of(tpu_comm, cfg)
    seq = synth._plan_for_algo(Algorithm.MULTIAXIS, operation.allreduce,
                               topo, 8 << 20, cfg)
    assert seq.shape == "multiaxis" and seq.param("shape2d") == coords_grid
    synth.validate_plan(seq)
    pipe = synth._gen_pipeline(
        operation.allreduce, topo, 8 << 20,
        synth.CostModel.from_config(cfg, topo.transport),
        cfg.sched_pipeline_chunks, cfg.sched_pipeline_startup_us)
    assert pipe.param("shape2d") == coords_grid
    assert pipe.param("pipeline_chunks") == cfg.sched_pipeline_chunks
    assert seq.predicted_us > plan.predicted_us
    synth.validate_plan(pipe)


_COLLECTIVE = re.compile(
    r"(all-reduce|reduce-scatter|all-gather)(-start)?\(")


def _collective_group_sizes(txt: str):
    """Group sizes of every collective in the module, read off the
    replica_groups annotations — the multi-axis schedule must run 2- and
    4-rank groups, never one flat 8-rank group."""
    sizes = []
    for m in re.finditer(r"replica_groups=\{\{(.*?)\}\}", txt):
        groups = m.group(1).split("},{")
        sizes.append(len(groups[0].split(",")))
    for m in re.finditer(r"replica_groups=\[\d+,(\d+)\]", txt):
        sizes.append(int(m.group(1)))
    return sizes


@pytest.mark.parametrize("chunks", [1, 4])
@pytest.mark.parametrize("op", ["allreduce", "reduce_scatter", "allgather"])
def test_multiaxis_program_lowers_per_axis(tpu_comm, op, chunks):
    """The synthesized schedule AOT-compiles for the real 2x4 mesh as
    ONE program whose collectives are per-axis (group sizes 2 and 4) —
    the torus decomposition survives to scheduled TPU code, sequential
    and chunk-pipelined alike (the pipelined allreduce still traces to
    one launch: the chunks are data-parallel lanes of one jitted
    shard_map program, not extra dispatches)."""
    n = 4096
    if op == "allreduce":
        fn = synth.build_multiaxis_allreduce(
            tpu_comm, (ROWS, COLS), reduceFunction.SUM, dataType.float32,
            pipeline_chunks=chunks)
        txt = _compile_text(fn, tpu_comm, (WORLD, n))
    elif op == "reduce_scatter":
        fn = synth.build_multiaxis_reduce_scatter(
            tpu_comm, (ROWS, COLS), reduceFunction.SUM, dataType.float32,
            pipeline_chunks=chunks)
        txt = _compile_text(fn, tpu_comm, (WORLD, WORLD * n))
    else:
        fn = synth.build_multiaxis_allgather(tpu_comm, (ROWS, COLS),
                                             pipeline_chunks=chunks)
        txt = _compile_text(fn, tpu_comm, (WORLD, n))
    assert _COLLECTIVE.search(txt), "no collective in the lowered module"
    sizes = _collective_group_sizes(txt)
    assert sizes, "no replica_groups annotations found"
    assert all(s in (ROWS, COLS) for s in sizes), \
        f"expected per-axis groups of {ROWS}/{COLS}, got {sizes}"
    assert any(s == COLS for s in sizes), f"heavy axis missing: {sizes}"


def test_declared_3axis_program_lowers_per_axis(tpu_comm):
    """A DECLARED (2, 2, 2) on the same 8 chips compiles a real 3-axis
    decomposition: every collective in the module runs 2-rank groups
    (all three axes have extent 2), still one program."""
    fn = synth.build_multiaxis_allreduce(
        tpu_comm, (2, 2, 2), reduceFunction.SUM, dataType.float32,
        pipeline_chunks=2)
    txt = _compile_text(fn, tpu_comm, (WORLD, 4096))
    assert _COLLECTIVE.search(txt), "no collective in the lowered module"
    sizes = _collective_group_sizes(txt)
    assert sizes and all(s == 2 for s in sizes), \
        f"expected 2-rank per-axis groups, got {sizes}"
