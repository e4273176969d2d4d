"""Topology-aware schedule synthesis (parallel/synth.py): cost-model
resolution, schedule-validity property tests, multi-axis program parity,
and the pre-refactor equivalence pins.

Three layers:

* **plan layer** — every candidate the generators emit passes the
  ownership-algebra validator (each (chunk, rank) covered exactly once,
  acyclic deps, hop counts matching the cost model), and corrupted
  plans are rejected;
* **resolution layer** — on an emulated 2x4 torus the cost model
  selects the multi-axis allreduce over the flat logical ring for
  large payloads, while single-axis meshes with default config resolve
  EXACTLY as the scalar ladder did before the refactor (the
  equivalence pins), and autotune-seeded registers stay binding;
* **program layer** — the multi-axis builders are bit-exact against
  the flat-ring and XLA paths (integer-valued operands), including the
  chunk-order realignment of reduce_scatter/allgather, padding, MAX,
  compressed wires, AUTO end-to-end dispatch and the CommandList
  one-launch path.
"""
import dataclasses
import os

import numpy as np
import pytest

import accl_tpu
from accl_tpu import Algorithm, dataType, reduceFunction
from accl_tpu.config import ACCLConfig, TransportBackend
from accl_tpu.constants import operation
from accl_tpu.obs import metrics
from accl_tpu.parallel import algorithms, synth

WORLD = 8


def _counter(key: str) -> float:
    return metrics.snapshot()["counters"].get(key, 0.0)


# ---------------------------------------------------------------------------
# topology resolution
# ---------------------------------------------------------------------------

def test_topology_declared_shape(accl):
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4])
    topo = synth.topology_of(comm, cfg)
    assert topo.axes == (2, 4) and topo.multi_axis and topo.world == WORLD
    with pytest.raises(ValueError, match="sched_mesh_shape"):
        synth.torus_shape(comm, accl.config.replace(sched_mesh_shape=[3, 4]))


def test_topology_default_single_axis(accl):
    """The CPU emulator mesh has no chip coords and no declaration:
    AUTO must never invent a torus (the factor2d fallback is reserved
    for explicit MULTIAXIS requests)."""
    comm = accl.global_comm()
    topo = synth.topology_of(comm, accl.config)
    assert topo.axes == (WORLD,) and not topo.multi_axis
    assert synth.torus_shape(comm, accl.config) is None
    assert synth.torus_shape(comm, accl.config,
                             allow_factor2d=True) == (2, 4)


class _FakeDev:
    def __init__(self, coords):
        self.coords = coords


def test_coords_shape_detection():
    """v5e-2x4-shaped coordinate grid -> (rows=2, cols=4); holes, dup
    cores and 1-D lines stay None."""
    grid = [_FakeDev((x, y, 0)) for y in range(2) for x in range(4)]
    assert synth._coords_shape(grid) == (2, 4)
    line = [_FakeDev((x, 0, 0)) for x in range(8)]
    assert synth._coords_shape(line) is None
    assert synth._coords_shape(grid[:-1] + [_FakeDev((0, 0, 0))]) is None
    assert synth._coords_shape([object()] * 4) is None  # no coords attr


def test_coords_shape_rejects_3d_grid():
    """A v4-style 2x2x2 slice has no single second axis whose rings are
    physical links — detection must NOT collapse y·z into "rows" (the
    independent-link-budget premise would be false there)."""
    cube = [_FakeDev((x, y, z))
            for z in range(2) for y in range(2) for x in range(2)]
    assert synth._coords_shape(cube) is None
    # and a grid whose x extent is 1 can't honor "cols = x extent"
    wall = [_FakeDev((0, y, z)) for z in range(2) for y in range(4)]
    assert synth._coords_shape(wall) is None


class _FakeComm:
    """Just enough communicator surface for topology_of/resolve: a
    device list with coords, an optional parent and the shrink-recovery
    ``degraded_from`` mark."""

    def __init__(self, devs, parent=None, degraded_from=None):
        self._devices = list(devs)
        self.world_size = len(self._devices)
        self.parent = parent
        self.degraded_from = degraded_from

    @property
    def devices(self):
        return list(self._devices)


def test_holed_grid_never_resolves_multiaxis():
    """Round-15 pin (survivor-subset planning): a 2x4 grid that lost one
    chip is NOT a torus — resolution must fall back to the single-axis
    logical ring over the survivors (never invent a multi-axis
    decomposition over missing links) and, on a shrink-built
    communicator, count the degraded decline."""
    holed = [_FakeDev((x, y, 0)) for y in range(2) for x in range(4)][:-1]
    assert synth._coords_shape(holed) is None
    assert synth._coords_degraded(holed)
    comm = _FakeComm(holed, degraded_from=8)   # built by a shrink recovery
    cfg = ACCLConfig(transport=TransportBackend.SIM)
    d0 = _counter('accl_select_decline_total{op="allreduce",'
                  'reason="holed_grid"}')
    plan = synth.resolve(operation.allreduce, 9 << 20, comm, cfg,
                         Algorithm.RING)
    assert plan.algorithm != Algorithm.MULTIAXIS
    assert plan.shape in ("ring", "kring")
    assert plan.topology.axes == (7,)          # the survivor ring
    assert _counter('accl_select_decline_total{op="allreduce",'
                    'reason="holed_grid"}') == d0 + 1
    # cached resolution does not re-count
    synth.resolve(operation.allreduce, 9 << 20, comm, cfg, Algorithm.RING)
    assert _counter('accl_select_decline_total{op="allreduce",'
                    'reason="holed_grid"}') == d0 + 1
    # an ORDINARY sub-group on the same holed coords (no shrink mark):
    # identical single-axis resolution, but routine group creation must
    # never count as a degradation event
    plain = _FakeComm(holed)
    plan2 = synth.resolve(operation.allreduce, 13 << 20, plain, cfg,
                          Algorithm.RING)
    assert plan2.algorithm != Algorithm.MULTIAXIS
    assert _counter('accl_select_decline_total{op="allreduce",'
                    'reason="holed_grid"}') == d0 + 1
    # the intact grid is NOT degraded (the counter is for real holes)
    full = [_FakeDev((x, y, 0)) for y in range(2) for x in range(4)]
    assert not synth._coords_degraded(full)
    # no-coords and 3-D slices are benign single-axis, never "degraded"
    assert not synth._coords_degraded([object()] * 4)
    cube = [_FakeDev((x, y, z))
            for z in range(2) for y in range(2) for x in range(2)]
    assert not synth._coords_degraded(cube)


def test_stale_declared_shape_on_shrunk_comm_counted():
    """A sched_mesh_shape declared for the pre-death world no longer
    matches the survivor-subset communicator: resolution falls back to
    single-axis (the sub-communicator rule) and the degraded decline is
    counted — but ONLY on the shrink-built group; an ordinary
    sub-communicator mismatching the global declaration stays benign."""
    devs = [object() for _ in range(7)]        # no coords (emulator rung)
    comm = _FakeComm(devs, parent=object(), degraded_from=8)
    cfg = ACCLConfig(transport=TransportBackend.SIM,
                     sched_mesh_shape=[2, 4])
    d0 = _counter('accl_select_decline_total{op="reduce_scatter",'
                  'reason="declared_shape_mismatch"}')
    plan = synth.resolve(operation.reduce_scatter, 11 << 20, comm, cfg,
                         Algorithm.RING)
    assert plan.algorithm != Algorithm.MULTIAXIS
    assert plan.topology.axes == (7,)
    assert _counter('accl_select_decline_total{op="reduce_scatter",'
                    'reason="declared_shape_mismatch"}') == d0 + 1
    # the routine case: same mismatch, no shrink mark, no count
    plain = _FakeComm([object() for _ in range(4)], parent=object())
    synth.resolve(operation.reduce_scatter, 11 << 20, plain, cfg,
                  Algorithm.RING)
    assert _counter('accl_select_decline_total{op="reduce_scatter",'
                    'reason="declared_shape_mismatch"}') == d0 + 1


def test_declared_shape_ignored_on_sub_communicator(accl):
    """cfg.sched_mesh_shape describes the GLOBAL mesh: a split
    sub-communicator with a different world must fall back to
    single-axis (legacy ladder), not crash select()."""
    cfg = accl.config.replace(sched_mesh_shape=[2, 4])
    sub = accl.global_comm().split([0, 1, 2, 3])
    assert synth.torus_shape(sub, cfg) is None
    topo = synth.topology_of(sub, cfg)
    assert topo.axes == (4,) and not topo.multi_axis
    # the full dispatch path resolves an algorithm instead of raising
    algo = algorithms.select(operation.allreduce, 4 << 20, sub, cfg)
    assert algo != Algorithm.MULTIAXIS


# ---------------------------------------------------------------------------
# plan layer: property tests over the whole candidate space
# ---------------------------------------------------------------------------

TOPOLOGIES = [(8,), (2, 4), (4, 2), (2, 2, 2), (4, 4), (3,)]


@pytest.mark.parametrize("axes", TOPOLOGIES)
@pytest.mark.parametrize("op", list(synth.SYNTH_OPS))
@pytest.mark.parametrize("nbytes", [1024, 1 << 22])
def test_all_candidates_validate(op, axes, nbytes):
    """Every schedule any generator emits, at every topology and size:
    (chunk, rank) coverage exactly once, acyclic step deps, per-axis
    hop counts matching the cost model's charge."""
    cfg = ACCLConfig()
    for bidir in (False, True):
        topo = synth.Topology(axes=tuple(axes),
                              transport=TransportBackend.SIM,
                              bidirectional=bidir)
        cands = synth.candidates(op, topo, nbytes, cfg)
        assert any(p.shape == "xla" for p in cands)
        if len(axes) >= 2:
            assert any(p.shape == "multiaxis" for p in cands)
        for plan in cands:
            synth.validate_plan(plan)
            assert plan.predicted_us > 0


def test_validator_rejects_cyclic_deps():
    topo = synth.Topology((2, 4), TransportBackend.SIM, True)
    plan = next(p for p in synth.candidates(
        operation.allreduce, topo, 1 << 20, ACCLConfig())
        if p.shape == "multiaxis")
    steps = list(plan.steps)
    steps[0] = dataclasses.replace(steps[0], deps=(1,))
    bad = dataclasses.replace(plan, steps=tuple(steps))
    with pytest.raises(ValueError, match="cyclic"):
        synth.validate_plan(bad)


def test_validator_rejects_hop_drift():
    """A step charging hops the shape's cost model would not — the α
    term silently drifting from the schedule — is a hard error."""
    topo = synth.Topology((2, 4), TransportBackend.SIM, True)
    plan = next(p for p in synth.candidates(
        operation.allreduce, topo, 1 << 20, ACCLConfig())
        if p.shape == "multiaxis")
    steps = list(plan.steps)
    steps[1] = dataclasses.replace(steps[1], hops=steps[1].hops + 1)
    bad = dataclasses.replace(plan, steps=tuple(steps))
    with pytest.raises(ValueError, match="hops"):
        synth.validate_plan(bad)


def test_validator_rejects_double_delivery():
    """Re-gathering an already-gathered payload delivers every chunk
    P times — the 'exactly once' half of the coverage property."""
    topo = synth.Topology((8,), TransportBackend.SIM, False)
    plan = next(p for p in synth.candidates(
        operation.allgather, topo, 4096, ACCLConfig())
        if p.shape == "ring")
    s0 = plan.steps[0]
    dup = dataclasses.replace(s0, index=1, deps=(0,))
    bad = dataclasses.replace(plan, steps=(s0, dup))
    with pytest.raises(ValueError, match="all_gather|delivered"):
        synth.validate_plan(bad)


def test_cost_model_ordering():
    """Sanity of the α-β formulas: the multi-axis schedule beats the
    flat logical ring at EVERY size on a 2x4 torus (equal wire time,
    8 vs 14 hop-steps), while XLA's log-depth single shot keeps small
    payloads; flat star is worst at large payloads."""
    cfg = ACCLConfig()
    topo = synth.Topology((2, 4), TransportBackend.SIM, True)

    def cost(shape, nbytes):
        return next(p for p in synth.candidates(
            operation.allreduce, topo, nbytes, cfg)
            if p.shape == shape).predicted_us

    for nbytes in (1024, 1 << 20, 64 << 20):
        assert cost("multiaxis", nbytes) < cost("kring", nbytes)
        assert cost("multiaxis", nbytes) < cost("ring", nbytes)
    assert cost("xla", 1024) < cost("multiaxis", 1024)
    assert cost("flat", 64 << 20) > cost("ring", 64 << 20)


# ---------------------------------------------------------------------------
# resolution layer
# ---------------------------------------------------------------------------

#: the pre-refactor select() decision table AT OR ABOVE the latency
#: threshold — single-axis meshes with default config MUST keep resolving
#: to exactly these (the equivalence pin of the ISSUE acceptance
#: criteria; sub-threshold payloads belong to the latency tier below)
_EQUIVALENCE = [
    (TransportBackend.SIM, operation.allreduce, 8 << 10, Algorithm.XLA),
    (TransportBackend.SIM, operation.allreduce, 64 << 10, Algorithm.XLA),
    (TransportBackend.SIM, operation.allreduce, 4 << 20, Algorithm.RING),
    (TransportBackend.SIM, operation.allreduce, 16 << 20, Algorithm.RING),
    (TransportBackend.SIM, operation.allreduce, 64 << 20,
     Algorithm.HIERARCHICAL),
    (TransportBackend.SIM, operation.allgather, 8 << 10, Algorithm.XLA),
    (TransportBackend.SIM, operation.allgather, 4 << 20, Algorithm.RING),
    (TransportBackend.SIM, operation.reduce_scatter, 8 << 10,
     Algorithm.XLA),
    (TransportBackend.SIM, operation.reduce_scatter, 4 << 20,
     Algorithm.RING),
    (TransportBackend.ICI, operation.allreduce, 1 << 20, Algorithm.PALLAS),
    (TransportBackend.ICI, operation.allgather, 1 << 20, Algorithm.PALLAS),
    (TransportBackend.ICI, operation.reduce_scatter, 8 << 20,
     Algorithm.PALLAS),
    (TransportBackend.ICI, operation.allreduce, 8 << 10, Algorithm.XLA),
    (TransportBackend.DCN, operation.allreduce, 4 << 20, Algorithm.RING),
]


@pytest.mark.parametrize("transport,op,nbytes,want", _EQUIVALENCE)
def test_single_axis_equivalence_pins(accl, transport, op, nbytes, want):
    """The refactor contract: with default config on a mesh with no
    declared/detected torus, select() returns what the scalar ladder
    alone returned before synthesis existed — for every payload at or
    above ``latency_tier_threshold`` (below it the latency tier may
    deviate; see the latency-tier tests)."""
    comm = accl.global_comm()
    cfg = accl.config.replace(transport=transport)
    assert nbytes >= cfg.latency_tier_threshold
    assert synth.torus_shape(comm, cfg) is None
    assert algorithms.select(op, nbytes, comm, cfg) == want
    # and byte-identical to the ladder itself
    assert algorithms.select(op, nbytes, comm, cfg) \
        == algorithms._select_legacy(op, nbytes, comm, cfg)


# ---------------------------------------------------------------------------
# the small-message latency tier (round 13)
# ---------------------------------------------------------------------------

def test_latency_tier_resolves_flat_below_threshold(accl):
    """Below ``latency_tier_threshold`` the α-dominated cost model rules:
    on this 8-rank mesh the 2-hop flat star beats XLA's 6-hop log-depth
    schedule for token-sized allreduces (arxiv 2403.18374: the algorithm
    choice flips at small sizes), on ANY topology — single-axis meshes
    included. The decision is attributable through the existing
    accl_sched_plan_total labels with source="latency_tier"."""
    comm = accl.global_comm()
    # a perturbed α forces fresh cache keys so the plan counter below
    # increments deterministically (the session plan cache is global)
    cfg = accl.config.replace(sched_alpha_us=1.0 + 2e-9)
    assert cfg.latency_tier_threshold == 8 * 1024
    key = ('accl_sched_plan_total{op="allreduce",shape="flat",'
           'source="latency_tier"}')
    before = _counter(key)
    for nbytes in (64, 1024, 8 * 1024 - 1):
        assert algorithms.select(operation.allreduce, nbytes, comm, cfg) \
            == Algorithm.FLAT
    assert _counter(key) > before
    # the boundary byte itself belongs to the legacy ladder (exclusive)
    assert algorithms.select(operation.allreduce, 8 * 1024, comm, cfg) \
        == Algorithm.XLA
    # the duals have no rooted flat/tree builders: the tier resolves the
    # log-depth single shot, still counted through the tier
    legacy = algorithms._select_legacy(operation.allgather, 1024, comm, cfg)
    plan = synth.resolve(operation.allgather, 1024, comm, cfg, legacy)
    assert plan.shape == "xla" and plan.source == "latency_tier"


def test_latency_tier_threshold_zero_disables(accl):
    """latency_tier_threshold=0 switches the tier off: sub-8KiB payloads
    resolve exactly as the scalar ladder again."""
    comm = accl.global_comm()
    off = accl.config.replace(latency_tier_threshold=0)
    for nbytes in (64, 1024):
        assert algorithms.select(operation.allreduce, nbytes, comm, off) \
            == Algorithm.XLA
        assert algorithms.select(operation.allreduce, nbytes, comm, off) \
            == algorithms._select_legacy(operation.allreduce, nbytes,
                                         comm, off)


def test_latency_tier_seed_override_pins_legacy(accl):
    """An autotune-seeded register pins the ladder below the threshold
    too — seeds are explicit overrides everywhere."""
    comm = accl.global_comm()
    cfg = accl.config.replace(ring_threshold=2 * 1024 * 1024)
    legacy = algorithms._select_legacy(operation.allreduce, 1024, comm, cfg)
    plan = synth.resolve(operation.allreduce, 1024, comm, cfg, legacy)
    assert plan.algorithm == legacy == Algorithm.XLA
    assert plan.source != "latency_tier"


def test_latency_tier_dcn_and_synthesis_off_keep_legacy(accl):
    """The DCN guard and the sched_synthesis switch outrank the tier."""
    comm = accl.global_comm()
    dcn = accl.config.replace(transport=TransportBackend.DCN)
    assert algorithms.select(operation.allreduce, 1024, comm, dcn) \
        == Algorithm.XLA
    off = accl.config.replace(sched_synthesis=False)
    assert algorithms.select(operation.allreduce, 1024, comm, off) \
        == Algorithm.XLA


def test_latency_tier_cache_key_splits_at_threshold(accl):
    """The threshold byte cuts INSIDE the <=16KiB size bucket, so tier
    membership must be part of the plan-cache key: a sub-threshold
    payload and its above-threshold bucket-mate resolve independently
    (the first caller must not poison the other's plan)."""
    comm = accl.global_comm()
    cfg = accl.config
    legacy = algorithms._select_legacy(operation.allreduce, 12 << 10,
                                       comm, cfg)
    above = synth.resolve(operation.allreduce, 12 << 10, comm, cfg, legacy)
    assert above.source == "legacy" and above.algorithm == Algorithm.XLA
    legacy2 = algorithms._select_legacy(operation.allreduce, 6 << 10,
                                        comm, cfg)
    below = synth.resolve(operation.allreduce, 6 << 10, comm, cfg, legacy2)
    assert below.source == "latency_tier"
    assert below.algorithm == Algorithm.FLAT
    # same bucket, different plans — and both stay cached independently
    assert metrics.size_bucket(12 << 10) == metrics.size_bucket(6 << 10)
    assert synth.resolve(operation.allreduce, 12 << 10, comm, cfg,
                         legacy) is above
    assert synth.resolve(operation.allreduce, 6 << 10, comm, cfg,
                         legacy2) is below


def test_resolve_multiaxis_on_emulated_2x4(accl):
    """THE acceptance pin: on an emulated 2x4 torus the cost model
    selects the synthesized multi-axis allreduce over the flat logical
    ring for every payload the ring used to own."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4])
    # the ring window [ring_threshold, hier_threshold) upgrades
    for nbytes in (4 << 20, 16 << 20, 63 << 20):
        assert algorithms.select(operation.allreduce, nbytes, comm, cfg) \
            == Algorithm.MULTIAXIS
    # small payloads ride the latency tier (α-dominated: the 2-hop flat
    # star beats log depth at this world size — round 13)
    assert algorithms.select(operation.allreduce, 1024, comm, cfg) \
        == Algorithm.FLAT
    # the very top of the range: sequential multiaxis TIES the two-tier
    # split (legacy kept pre-pipelining), but the chunk-pipelined
    # candidate strictly beats both — the overlap win the sequential
    # phases could never claim
    assert algorithms.select(operation.allreduce, 128 << 20, comm, cfg) \
        == Algorithm.MULTIAXIS
    legacy = algorithms._select_legacy(operation.allreduce, 128 << 20,
                                       comm, cfg)
    top = synth.resolve(operation.allreduce, 128 << 20, comm, cfg, legacy)
    assert top.shape == "pipeline"
    # ... and with pipelining off (sched_pipeline_chunks=1) the tie
    # resolves EXACTLY as pre-refactor: legacy HIERARCHICAL kept
    seq = cfg.replace(sched_pipeline_chunks=1)
    assert algorithms.select(operation.allreduce, 128 << 20, comm, seq) \
        == Algorithm.HIERARCHICAL
    # the dual ops ride the same window (per-op byte conventions)
    assert algorithms.select(operation.allgather, 4 << 20, comm, cfg) \
        == Algorithm.MULTIAXIS
    assert algorithms.select(operation.reduce_scatter, 4 << 20, comm, cfg) \
        == Algorithm.MULTIAXIS


def test_resolve_seed_override_pins_legacy(accl):
    """A register that differs from its default is an autotune seed /
    operator hand tune: the legacy decision stays binding even on a
    declared torus (the override/migration contract)."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4],
                              ring_threshold=64 * 1024)
    got = algorithms.select(operation.allreduce, 4 << 20, comm, cfg)
    assert got == Algorithm.RING
    legacy = algorithms._select_legacy(operation.allreduce, 4 << 20, comm,
                                       cfg)
    plan = synth.resolve(operation.allreduce, 4 << 20, comm, cfg, legacy)
    assert plan.source == "override" and plan.algorithm == Algorithm.RING
    # an UNRELATED op's seed does not pin this op
    cfg2 = accl.config.replace(sched_mesh_shape=[2, 4],
                               ag_ring_threshold=64 * 1024)
    assert algorithms.select(operation.allreduce, 4 << 20, comm, cfg2) \
        == Algorithm.MULTIAXIS


def test_resolve_synthesis_off_and_dcn_keep_legacy(accl):
    comm = accl.global_comm()
    off = accl.config.replace(sched_mesh_shape=[2, 4],
                              sched_synthesis=False)
    assert algorithms.select(operation.allreduce, 8 << 20, comm, off) \
        == Algorithm.RING
    # the DCN two-tier story stays with the host-aligned hierarchical
    # path — synthesis never deviates on DCN transports
    dcn = accl.config.replace(sched_mesh_shape=[2, 4],
                              transport=TransportBackend.DCN)
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       dcn)
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, dcn, legacy)
    assert plan.source == "legacy" and plan.algorithm == legacy


def test_resolve_caches_and_counts(accl):
    """Plans are memoized per (op, topology, size-bucket, legacy, cost
    params) and the telemetry tier records both the cache traffic and
    one plan-resolution counter per synthesized plan, keyed by the
    chosen schedule shape."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4],
                              sched_alpha_us=1.0 + 1e-9)  # fresh cache keys
    hit_k = 'accl_sched_plan_cache_total{event="hit"}'
    miss_k = 'accl_sched_plan_cache_total{event="miss"}'
    plan_k = ('accl_sched_plan_total{op="allreduce",shape="pipeline",'
              'source="cost_model"}')
    h0, m0, p0 = _counter(hit_k), _counter(miss_k), _counter(plan_k)
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       cfg)
    p1 = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    p2 = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    assert p1 is p2  # the cached object itself
    # default config pipelines (sched_pipeline_chunks=4): the plan
    # counter carries the pipelined shape label
    assert p1.shape == "pipeline" and p1.source == "cost_model"
    assert p1.param("pipeline_chunks") == 4
    assert _counter(miss_k) == m0 + 1
    assert _counter(hit_k) == h0 + 1
    assert _counter(plan_k) == p0 + 1  # one per synthesized plan, not per call
    # the session hook drops the cache (fresh sessions re-synthesize)
    synth.reset_plan_cache()
    p3 = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    assert p3 is not p1 and p3 == p1


def test_plan_describe_names_schedule(accl):
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4])
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       cfg)
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    d = plan.describe()
    assert "multiaxis" in d and "reduce_scatter" in d and "all_gather" in d
    assert plan.param("shape2d") == (2, 4)


# ---------------------------------------------------------------------------
# select() decline visibility (satellite)
# ---------------------------------------------------------------------------

def test_dcn_decline_counted(accl):
    """The DCN hierarchical early-engage silently fell through when the
    mesh is not host-aligned; now every decline is counted (op +
    reason), mirroring the accl_cmatmul_fallback_total discipline."""
    comm = accl.global_comm()
    assert comm.hosts_shape() is None
    dcn = accl.config.replace(transport=TransportBackend.DCN)
    key = ('accl_select_decline_total{op="allreduce",'
           'reason="dcn_no_host_shape"}')
    before = _counter(key)
    for _ in range(3):
        got = algorithms.select(operation.allreduce,
                                dcn.dcn_hier_threshold, comm, dcn)
        assert got != Algorithm.HIERARCHICAL
    assert _counter(key) - before == 3.0  # every occurrence, no dedupe


def test_prime_world_hier_decline_counted(accl):
    """The generic hier engage point's decline (no 2-D factorization)
    is attributable too."""
    comm = accl.global_comm().split(range(7))
    key = 'accl_select_decline_total{op="allreduce",reason="no_2d_shape"}'
    before = _counter(key)
    got = algorithms.select(operation.allreduce, accl.config.hier_threshold,
                            comm, accl.config)
    assert got == Algorithm.RING  # falls through to the ring edge
    assert _counter(key) - before == 1.0


# ---------------------------------------------------------------------------
# program layer: parity of the multi-axis builders
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("count", [64, 100])  # incl. the padding path
def test_multiaxis_allreduce_bit_exact(accl, rng, count):
    dt = dataType.float32
    data = rng.integers(-100, 100, (WORLD, count)).astype(np.float32)
    outs = {}
    for algo in (Algorithm.RING, Algorithm.XLA, Algorithm.MULTIAXIS):
        send = accl.create_buffer(count, dt)
        recv = accl.create_buffer(count, dt)
        send.host[:] = data
        accl.allreduce(send, recv, count, reduceFunction.SUM,
                       algorithm=algo)
        outs[algo] = recv.host.copy()
    np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS],
                                  outs[Algorithm.RING])
    np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS],
                                  outs[Algorithm.XLA])
    np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS][0],
                                  data.sum(0))


def test_multiaxis_allreduce_max(accl, rng):
    count, dt = 48, dataType.int32
    data = rng.integers(-100, 100, (WORLD, count)).astype(np.int32)
    send = accl.create_buffer(count, dt)
    recv = accl.create_buffer(count, dt)
    send.host[:] = data
    accl.allreduce(send, recv, count, reduceFunction.MAX,
                   algorithm=Algorithm.MULTIAXIS)
    for r in range(WORLD):
        np.testing.assert_array_equal(recv.host[r], data.max(0))


def test_multiaxis_reduce_scatter_bit_exact(accl, rng):
    """The chunk-order realignment: rank (r, c) must land FLAT chunk
    r*cols+c — bit-identical to the 1-D ring path."""
    count, dt = 48, dataType.int32
    data = rng.integers(-50, 50, (WORLD, count * WORLD)).astype(np.int32)
    outs = {}
    for algo in (Algorithm.RING, Algorithm.MULTIAXIS):
        send = accl.create_buffer(count * WORLD, dt)
        recv = accl.create_buffer(count, dt)
        send.host[:] = data
        accl.reduce_scatter(send, recv, count, reduceFunction.SUM,
                            algorithm=algo)
        outs[algo] = recv.host.copy()
    np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS],
                                  outs[Algorithm.RING])
    for r in range(WORLD):
        np.testing.assert_array_equal(
            outs[Algorithm.MULTIAXIS][r],
            data[:, r * count:(r + 1) * count].sum(0))


def test_multiaxis_allgather_bit_exact(accl, rng):
    count, dt = 33, dataType.float32
    data = rng.standard_normal((WORLD, count)).astype(np.float32)
    outs = {}
    for algo in (Algorithm.RING, Algorithm.MULTIAXIS):
        send = accl.create_buffer(count, dt)
        recv = accl.create_buffer(count * WORLD, dt)
        send.host[:] = data
        accl.allgather(send, recv, count, algorithm=algo)
        outs[algo] = recv.host.copy()
    np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS],
                                  outs[Algorithm.RING])
    for r in range(WORLD):
        np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS][r],
                                      data.reshape(-1))


def test_multiaxis_compressed_wire(accl, rng):
    """Per-hop wire compression rides the multi-axis schedule like any
    other: bf16 on every hop, folds at full precision."""
    count, dt = 64, dataType.float32
    data = rng.integers(-100, 100, (WORLD, count)).astype(np.float32)
    send = accl.create_buffer(count, dt)
    recv = accl.create_buffer(count, dt)
    send.host[:] = data
    accl.allreduce(send, recv, count, reduceFunction.SUM,
                   compress_dtype=dataType.bfloat16,
                   algorithm=Algorithm.MULTIAXIS)
    expect = data.astype(np.float64).sum(0)
    for r in range(WORLD):
        np.testing.assert_allclose(recv.host[r], expect, rtol=0.1, atol=2.0)


def test_auto_dispatches_multiaxis_end_to_end(accl, rng):
    """AUTO on a declared 2x4 torus at a ring-window payload: the call
    dispatches the synthesized schedule (selection counter) and the
    result is exact."""
    count = 1 << 20  # 4 MiB f32 — the ring window's lower edge
    dt = dataType.float32
    saved = accl.config
    accl.config = saved.replace(sched_mesh_shape=[2, 4])
    try:
        key = ('accl_algorithm_selected_total{op="allreduce",'
               'algorithm="multiaxis"}')
        before = _counter(key)
        data = rng.integers(-8, 8, (WORLD, count)).astype(np.float32)
        send = accl.create_buffer(count, dt)
        recv = accl.create_buffer(count, dt)
        send.host[:] = data
        accl.allreduce(send, recv, count, reduceFunction.SUM)
        assert _counter(key) > before
        np.testing.assert_array_equal(recv.host[0], data.sum(0))
    finally:
        accl.config = saved


def test_cmdlist_multiaxis_one_launch(accl, rng):
    """A synthesized schedule recorded in a CommandList compiles into
    the ONE-launch composite and caches like any per-op program."""
    count, dt = 64, dataType.float32
    data = rng.integers(-100, 100, (WORLD, count)).astype(np.float32)
    send = accl.create_buffer(count, dt)
    recv = accl.create_buffer(count, dt)
    send.host[:] = data
    key = 'accl_cmdlist_executes_total{steps="2"}'
    before = _counter(key)
    cl = accl.command_list()
    cl.allreduce(send, recv, count, reduceFunction.SUM,
                 algorithm=Algorithm.MULTIAXIS)
    cl.allgather(recv, accl.create_buffer(count * WORLD, dt), count,
                 algorithm=Algorithm.MULTIAXIS)
    cl.execute()
    assert _counter(key) == before + 1
    np.testing.assert_array_equal(recv.host[0], data.sum(0))


def test_multiaxis_requires_composite_world(accl):
    comm = accl.global_comm().split(range(7))
    with pytest.raises(ValueError, match="composite world"):
        algorithms.build_allreduce(comm, reduceFunction.SUM,
                                   dataType.float32, Algorithm.MULTIAXIS,
                                   None)


def test_explicit_multiaxis_supported_everywhere_it_claims():
    for op in synth.SYNTH_OPS:
        assert algorithms.supported(op, Algorithm.MULTIAXIS)
    assert not algorithms.supported(operation.bcast, Algorithm.MULTIAXIS)


# ---------------------------------------------------------------------------
# ProgramCache LRU bound (satellite)
# ---------------------------------------------------------------------------

def test_program_cache_lru_bound_and_metrics():
    from accl_tpu.parallel.compiler import ProgramCache

    pc = ProgramCache(maxsize=2)
    hit_k = 'accl_program_cache_total{event="hit"}'
    evict_k = 'accl_program_cache_total{event="evict"}'
    h0, e0 = _counter(hit_k), _counter(evict_k)
    pc.get("a", lambda: "A")
    pc.get("b", lambda: "B")
    assert pc.get("a", lambda: "FRESH") == "A"   # refreshes a's recency
    pc.get("c", lambda: "C")                     # evicts b (LRU)
    assert len(pc) == 2 and pc.evictions == 1
    assert pc.get("b", lambda: "B2") == "B2"     # b was evicted, rebuilt
    assert _counter(hit_k) == h0 + 1
    assert _counter(evict_k) - e0 == 2           # c evicted b; b evicted a
    assert metrics.snapshot()["gauges"]["accl_program_cache_size"] == 2.0
    size, hits, misses = pc.stats()
    assert (size, hits, misses) == (2, 1, 4)
    # shrinking the bound evicts immediately (config write-through path)
    pc.set_maxsize(1)
    assert len(pc) == 1 and pc.evictions == 3
    # 0 disables the bound
    pc.set_maxsize(0)
    for i in range(10):
        pc.get(("k", i), lambda: i)
    assert len(pc) == 11


def test_program_cache_config_write_through():
    import jax

    acc = accl_tpu.ACCL(devices=jax.devices()[:1])
    try:
        assert acc._programs.maxsize == acc.config.program_cache_size
        acc.config = acc.config.replace(program_cache_size=7)
        assert acc._programs.maxsize == 7
        st = acc.stats()["program_cache"]
        assert st["max_size"] == 7 and "evictions" in st
    finally:
        acc.deinit()


def test_config_roundtrip_with_sched_fields():
    """The new registers survive the exact-schema save/load contract
    (sched_mesh_shape serializes as a JSON list)."""
    cfg = ACCLConfig(sched_mesh_shape=[2, 4], sched_alpha_us=0.5,
                     program_cache_size=33)
    back = ACCLConfig.from_json(cfg.to_json())
    assert back.sched_mesh_shape == [2, 4]
    assert back.sched_alpha_us == 0.5
    assert back.program_cache_size == 33
    assert back.sched_synthesis is True


# ---------------------------------------------------------------------------
# round 16: chunked phase pipelining + N-D declarations + full authority
# ---------------------------------------------------------------------------

def test_topology_declared_3d(accl):
    """A DECLARED [2, 2, 2] is a real 3-axis topology (the generators
    and validator always handled N axes; the builders now do too) —
    while coords-inferred 3-D stays refused (test above) and malformed
    declarations fail loudly."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 2, 2])
    topo = synth.topology_of(comm, cfg)
    assert topo.axes == (2, 2, 2) and topo.multi_axis
    assert synth.torus_shape(comm, cfg) == (2, 2, 2)
    with pytest.raises(ValueError, match="sched_mesh_shape"):
        synth.torus_shape(comm, accl.config.replace(sched_mesh_shape=[8]))
    with pytest.raises(ValueError, match="sched_mesh_shape"):
        synth.torus_shape(comm, accl.config.replace(
            sched_mesh_shape=[8, 1]))


def _pipeline_plan(op=operation.allreduce, axes=(2, 4), nbytes=8 << 20,
                   chunks=4, bidir=True):
    topo = synth.Topology(tuple(axes), TransportBackend.SIM, bidir)
    model = synth.CostModel.from_config(ACCLConfig(), topo.transport)
    return synth._gen_pipeline(op, topo, synth._payload_total(
        op, nbytes, topo.world), model, chunks, 2.0)


@pytest.mark.parametrize("axes", [(2, 4), (4, 2), (2, 2, 2), (4, 4)])
@pytest.mark.parametrize("op", list(synth.SYNTH_OPS))
def test_pipeline_plans_validate(op, axes):
    """Every pipelined plan passes the per-chunk ownership algebra:
    each (chunk, axis-phase) folded/delivered exactly once, per-chunk
    deps acyclic, hops matching the sequential per-axis rings."""
    for chunks in (2, 3, 4):
        plan = _pipeline_plan(op, axes, chunks=chunks)
        assert plan is not None and plan.shape == "pipeline"
        assert plan.algorithm == Algorithm.MULTIAXIS
        assert plan.param("pipeline_chunks") == chunks
        synth.validate_plan(plan)
        # chunks=1 generates no pipelined candidate at all
    assert _pipeline_plan(op, axes, chunks=1) is None
    # ... and neither does a single-axis topology
    assert _pipeline_plan(op, (8,), chunks=4) is None


def test_validator_rejects_cross_chunk_double_fold():
    """A step relabeled into another chunk's lane folds that chunk's
    phase twice (and leaves its own lane incomplete) — the cross-chunk
    aliasing the per-chunk algebra exists to catch."""
    plan = _pipeline_plan(chunks=2)
    steps = list(plan.steps)
    n_ph = len(steps) // 2
    # chunk 1's first phase pretends to be chunk 0's: chunk 0 now runs
    # its reduce_scatter twice
    steps[n_ph] = dataclasses.replace(steps[n_ph], chunk=0)
    bad = dataclasses.replace(plan, steps=tuple(steps))
    with pytest.raises(ValueError, match="pipeline chunk"):
        synth.validate_plan(bad)


def test_validator_rejects_chunk_out_of_phase_order():
    """A chunk whose all-gather is ordered before its reduce-scatter
    (deps flipped) delivers fully-owned chunks into ranks that already
    hold them — phase order is provable, not stylistic."""
    plan = _pipeline_plan(op=operation.allreduce, chunks=2)
    steps = list(plan.steps)
    n_ph = len(steps) // 2
    # flip chunk 0's intra-chunk dependency chain: the last phase (an
    # all_gather) becomes the root, the first (a reduce_scatter) waits
    # on it — the topological order then gathers before scattering
    head = steps[0]
    tail = steps[n_ph - 1]
    steps[0] = dataclasses.replace(head, deps=(tail.index,))
    steps[n_ph - 1] = dataclasses.replace(tail, deps=())
    for i in range(1, n_ph - 1):
        steps[i] = dataclasses.replace(steps[i], deps=(steps[i].index - 1,))
    bad = dataclasses.replace(plan, steps=tuple(steps))
    with pytest.raises(ValueError, match="pipeline chunk 0"):
        synth.validate_plan(bad)


def test_validator_rejects_pipeline_hop_drift():
    """A pipelined step charging hops the per-axis ring would not —
    chunking splits bytes, never hops."""
    plan = _pipeline_plan(chunks=3)
    steps = list(plan.steps)
    steps[2] = dataclasses.replace(steps[2], hops=steps[2].hops + 1)
    bad = dataclasses.replace(plan, steps=tuple(steps))
    with pytest.raises(ValueError, match="hops"):
        synth.validate_plan(bad)


def test_validator_rejects_missing_chunk_lane():
    """A declared chunk count whose lanes do not all appear (a dropped
    chunk would silently skip part of the payload)."""
    plan = _pipeline_plan(chunks=3)
    n_ph = len(plan.steps) // 3
    bad = dataclasses.replace(plan, steps=plan.steps[:2 * n_ph])
    with pytest.raises(ValueError, match="declared range"):
        synth.validate_plan(bad)
    # mixed chunked/unchunked steps are unaccountable
    steps = list(plan.steps)
    steps[0] = dataclasses.replace(steps[0], chunk=None)
    bad2 = dataclasses.replace(plan, steps=tuple(steps))
    with pytest.raises(ValueError, match="mixed"):
        synth.validate_plan(bad2)


def test_pipeline_cost_formula():
    """The pipelined candidate costs exactly
    max(phase costs) + (chunks-1)·startup, and resolve() prefers it
    over the sequential schedule exactly where that undercuts the
    sequential sum."""
    topo = synth.Topology((2, 4), TransportBackend.SIM, True)
    model = synth.CostModel.from_config(ACCLConfig(), topo.transport)
    for nbytes in (1 << 16, 1 << 20, 8 << 20):
        N = synth._payload_total(operation.allreduce, nbytes, topo.world)
        seq = synth._gen_multiaxis(operation.allreduce, topo, N, model)
        for chunks, startup in ((2, 2.0), (4, 2.0), (4, 50.0)):
            pipe = synth._gen_pipeline(operation.allreduce, topo, N,
                                       model, chunks, startup)
            phase_costs = [model.step_us(s.hops, s.link_bytes, s.channels)
                           for s in seq.steps]
            want = max(phase_costs) + (chunks - 1) * startup
            assert pipe.predicted_us == pytest.approx(want)
            assert (pipe.predicted_us < seq.predicted_us) \
                == (want < seq.predicted_us)
    # an absurd startup term prices pipelining out: resolve keeps the
    # sequential multiaxis schedule
    cfg = ACCLConfig(transport=TransportBackend.SIM,
                     sched_mesh_shape=[2, 4],
                     sched_pipeline_startup_us=1e6)
    comm = _FakeComm([object()] * 8)
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, cfg,
                         Algorithm.RING)
    assert plan.shape == "multiaxis"


_MIB = 1 << 20
_BYTES_OPS = (operation.allreduce, operation.reduce_scatter,
              operation.allgather)


def _nccl_nbytes(op, S, world):
    """select()'s byte convention for nccl-tests' per-rank S (the
    allreduce buffer, the reduce-scatter input, the allgather output)."""
    return S // world if op == operation.allgather else S


@pytest.mark.parametrize("S", [16 * _MIB, 128 * _MIB, 1024 * _MIB])
@pytest.mark.parametrize("op", _BYTES_OPS, ids=lambda o: o.name)
@pytest.mark.parametrize("shape", [[2, 2], [2, 4]], ids=["2x2", "2x4"])
def test_ici_multiaxis_window_resolves_xla(shape, op, S):
    """On an ICI torus XLA's own collective is the window's baseline
    and only the ladder's plan challenges it, which must strictly beat
    it — so AUTO keeps XLA, decided by the cost model. Explicit
    MULTIAXIS still runs."""
    world = shape[0] * shape[1]
    comm = _FakeComm([object()] * world)
    cfg = ACCLConfig(transport=TransportBackend.ICI, sched_mesh_shape=shape)
    nbytes = _nccl_nbytes(op, S, world)
    assert algorithms.select(op, nbytes, comm, cfg) == Algorithm.XLA
    legacy = algorithms._select_legacy(op, nbytes, comm, cfg)
    plan = synth.resolve(op, nbytes, comm, cfg, legacy)
    assert plan.shape == "xla" and plan.source == "cost_model"
    assert plan.algorithm == Algorithm.XLA
    assert plan.topology.axes == tuple(shape)
    synth.validate_plan(plan)
    assert algorithms.select(op, nbytes, comm, cfg,
                             requested=Algorithm.MULTIAXIS) \
        == Algorithm.MULTIAXIS


def test_ici_window_counts_xla_plan():
    """The decision is visible in the existing plan counter: shape
    ``xla``, source ``cost_model``, once per synthesized plan."""
    comm = _FakeComm([object()] * 4)
    cfg = ACCLConfig(transport=TransportBackend.ICI, sched_mesh_shape=[2, 2],
                     sched_alpha_us=1.0 + 3e-9)   # fresh cache keys
    key = ('accl_sched_plan_total{op="reduce_scatter",shape="xla",'
           'source="cost_model"}')
    before = _counter(key)
    for _ in range(3):
        algorithms.select(operation.reduce_scatter, 64 * _MIB, comm, cfg)
    assert _counter(key) == before + 1


@pytest.mark.parametrize("axes", [(2, 2), (2, 4), (4, 4), (2, 2, 2)])
def test_ici_sequential_multiaxis_never_undercuts_xla(axes):
    """Why the ICI window leaves the sequential multi-axis shape out:
    it moves XLA's bytes (N·(P-1)/P per sweep) in Σ(sᵢ-1) ≥ ⌈log2 P⌉
    hops, so under the model it at best ties XLA (up to rounding)."""
    topo = synth.Topology(axes, TransportBackend.ICI, True)
    model = synth.CostModel.from_config(
        ACCLConfig(transport=TransportBackend.ICI), topo.transport)
    for op in _BYTES_OPS:
        for S in (16 * _MIB, 128 * _MIB, 1024 * _MIB):
            N = synth._payload_total(op, S, topo.world)
            xla = synth._gen_xla(op, topo, N, model)
            seq = synth._gen_multiaxis(op, topo, N, model)
            assert seq.predicted_us >= xla.predicted_us * (1 - 1e-12)


@pytest.mark.parametrize("wire", [dataType.float16, dataType.bfloat16],
                         ids=lambda d: d.name)
@pytest.mark.parametrize("shape", [[2, 2], [2, 4]], ids=["2x2", "2x4"])
def test_ici_compressed_allreduce_keeps_multiaxis(shape, wire):
    """An fp32 allreduce with a casting wire decompresses before its
    arithmetic, so XLA's path would all-gather the whole buffer: the
    ICI window does not offer XLA there (``xla_gathers``), and the
    multi-axis candidates compete against the ladder as before — a
    128 MiB call still resolves the chunk-pipelined MULTIAXIS plan."""
    from accl_tpu.arithconfig import DEFAULT_ARITH_CONFIG
    arith = DEFAULT_ARITH_CONFIG[(dataType.float32, wire)]
    assert arith.decompress_before_arith
    world = shape[0] * shape[1]
    comm = _FakeComm([object()] * world)
    cfg = ACCLConfig(transport=TransportBackend.ICI, sched_mesh_shape=shape)
    algo, plan = algorithms.select_plan(operation.allreduce, 128 * _MIB,
                                        comm, cfg, xla_gathers=True)
    assert algo == Algorithm.MULTIAXIS
    assert plan.shape == "pipeline" and plan.source == "cost_model"
    assert plan.param("shape2d") == tuple(shape)
    synth.validate_plan(plan)
    algo, plan = algorithms.select_plan(operation.allreduce, 128 * _MIB,
                                        comm, cfg)
    assert algo == Algorithm.XLA and plan.shape == "xla"


@pytest.mark.parametrize("wire", [dataType.float16, dataType.bfloat16],
                         ids=lambda d: d.name)
def test_session_marks_compressed_allreduce_xla_gathers(accl, wire):
    """The dispatch layer feeds the call's arith into the ICI window: on
    a declared 2x4 a compressed fp32 allreduce dispatches MULTIAXIS,
    the same call uncompressed dispatches XLA (spec only, no build)."""
    saved = accl.config
    accl.config = saved.replace(transport=TransportBackend.ICI,
                                sched_mesh_shape=[2, 4])
    count = 32 * _MIB                   # 128 MiB of f32 per rank
    comm = accl.global_comm()
    try:
        for compress, algo in ((wire, "multiaxis"), (None, "xla")):
            key = ('accl_algorithm_selected_total{op="allreduce",'
                   f'algorithm="{algo}"}}')
            before = _counter(key)
            accl._spec_allreduce(comm, count, dataType.float32,
                                 reduceFunction.SUM, compress, None)
            assert _counter(key) == before + 1
    finally:
        accl.config = saved


def test_pipeline_chunks_1_resolution_byte_identical(accl):
    """THE equivalence pin: sched_pipeline_chunks=1 resolves EXACTLY
    as the pre-pipelining refactor — sequential multiaxis in the ring
    window, legacy at the hier tie — for every op."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4],
                              sched_pipeline_chunks=1)
    for op, nbytes in ((operation.allreduce, 8 << 20),
                       (operation.allgather, 4 << 20),
                       (operation.reduce_scatter, 4 << 20)):
        legacy = algorithms._select_legacy(op, nbytes, comm, cfg)
        plan = synth.resolve(op, nbytes, comm, cfg, legacy)
        assert plan.shape == "multiaxis" and plan.source == "cost_model"
        assert plan.algorithm == Algorithm.MULTIAXIS
    # the hier tie at the top of the range keeps legacy (pre-refactor)
    legacy = algorithms._select_legacy(operation.allreduce, 128 << 20,
                                       comm, cfg)
    plan = synth.resolve(operation.allreduce, 128 << 20, comm, cfg, legacy)
    assert plan.source == "cost_model" and plan.algorithm == legacy


def test_resolve_pipeline_3d_declared(accl):
    """A declared (2,2,2) resolves the pipelined 3-axis schedule in the
    bandwidth window — the N-D dispatch the builders now honor."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 2, 2])
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       cfg)
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    assert plan.shape == "pipeline"
    assert plan.param("shape2d") == (2, 2, 2)
    assert len({s.axis for s in plan.steps}) == 3
    synth.validate_plan(plan)
    assert algorithms.select(operation.allreduce, 8 << 20, comm, cfg) \
        == Algorithm.MULTIAXIS


def test_pipeline_seed_override_still_pins(accl):
    """Autotune seeds outrank the pipelined candidate exactly as they
    outrank the sequential one."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4],
                              ring_threshold=64 * 1024)
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       cfg)
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    assert plan.source == "override" and plan.shape != "pipeline"


# ---------------------------------------------------------------------------
# program parity: pipelined + 3-axis builders
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(2, 4), (4, 2), (2, 2, 2)])
@pytest.mark.parametrize("chunks", [2, 3])
def test_pipelined_allreduce_bit_exact(accl, rng, shape, chunks):
    """Pipelined + N-D: bit-exact vs the flat-ring and XLA paths,
    including the padding path (count=100 is not divisible by
    world*chunks)."""
    dt = dataType.float32
    for count in (64, 100):
        data = rng.integers(-100, 100, (WORLD, count)).astype(np.float32)
        outs = {}
        saved = accl.config
        accl.config = saved.replace(sched_mesh_shape=list(shape),
                                    sched_pipeline_chunks=chunks)
        try:
            for algo in (Algorithm.RING, Algorithm.XLA,
                         Algorithm.MULTIAXIS):
                send = accl.create_buffer(count, dt)
                recv = accl.create_buffer(count, dt)
                send.host[:] = data
                accl.allreduce(send, recv, count, reduceFunction.SUM,
                               algorithm=algo)
                outs[algo] = recv.host.copy()
        finally:
            accl.config = saved
        np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS],
                                      outs[Algorithm.RING])
        np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS],
                                      outs[Algorithm.XLA])
        np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS][0],
                                      data.sum(0))


@pytest.mark.parametrize("shape", [(2, 4), (2, 2, 2)])
def test_pipelined_duals_and_max_bit_exact(accl, rng, shape):
    """reduce_scatter / allgather / MAX under chunking: the chunk
    re-interleaving must land every rank exactly its flat block."""
    saved = accl.config
    accl.config = saved.replace(sched_mesh_shape=list(shape),
                                sched_pipeline_chunks=3)
    try:
        count = 48  # not divisible by 3*world: padding inside each block
        data = rng.integers(-50, 50, (WORLD, count * WORLD)).astype(np.int32)
        outs = {}
        for algo in (Algorithm.RING, Algorithm.MULTIAXIS):
            send = accl.create_buffer(count * WORLD, dataType.int32)
            recv = accl.create_buffer(count, dataType.int32)
            send.host[:] = data
            accl.reduce_scatter(send, recv, count, reduceFunction.SUM,
                                algorithm=algo)
            outs[algo] = recv.host.copy()
        np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS],
                                      outs[Algorithm.RING])
        # allgather, odd count
        g = rng.standard_normal((WORLD, 33)).astype(np.float32)
        outs = {}
        for algo in (Algorithm.RING, Algorithm.MULTIAXIS):
            send = accl.create_buffer(33, dataType.float32)
            recv = accl.create_buffer(33 * WORLD, dataType.float32)
            send.host[:] = g
            accl.allgather(send, recv, 33, algorithm=algo)
            outs[algo] = recv.host.copy()
        np.testing.assert_array_equal(outs[Algorithm.MULTIAXIS],
                                      outs[Algorithm.RING])
        # MAX rides the monotone-cast fast path under chunking too
        m = rng.integers(-100, 100, (WORLD, 40)).astype(np.int32)
        send = accl.create_buffer(40, dataType.int32)
        recv = accl.create_buffer(40, dataType.int32)
        send.host[:] = m
        accl.allreduce(send, recv, 40, reduceFunction.MAX,
                       algorithm=Algorithm.MULTIAXIS)
        for r in range(WORLD):
            np.testing.assert_array_equal(recv.host[r], m.max(0))
    finally:
        accl.config = saved


def test_pipelined_compressed_wire(accl, rng):
    """bf16 wire staging through the pipelined 3-axis schedule: every
    hop compressed, folds at full precision, tolerance bounded."""
    saved = accl.config
    accl.config = saved.replace(sched_mesh_shape=[2, 2, 2],
                                sched_pipeline_chunks=2)
    try:
        count = 64
        data = rng.integers(-100, 100, (WORLD, count)).astype(np.float32)
        send = accl.create_buffer(count, dataType.float32)
        recv = accl.create_buffer(count, dataType.float32)
        send.host[:] = data
        accl.allreduce(send, recv, count, reduceFunction.SUM,
                       compress_dtype=dataType.bfloat16,
                       algorithm=Algorithm.MULTIAXIS)
        expect = data.astype(np.float64).sum(0)
        for r in range(WORLD):
            np.testing.assert_allclose(recv.host[r], expect, rtol=0.1,
                                       atol=2.0)
    finally:
        accl.config = saved


def test_auto_dispatches_pipelined_end_to_end(accl, rng):
    """AUTO on a declared 2x4 at a ring-window payload under the default
    chunked config: the resolved plan is the pipelined shape, the
    dispatched program runs it (chunk count in the program key), and
    the result is exact."""
    count = 1 << 20  # 4 MiB f32
    saved = accl.config
    accl.config = saved.replace(sched_mesh_shape=[2, 4])
    try:
        key = ('accl_sched_plan_total{op="allreduce",shape="pipeline",'
               'source="cost_model"}')
        before = _counter(key)
        data = rng.integers(-8, 8, (WORLD, count)).astype(np.float32)
        send = accl.create_buffer(count, dataType.float32)
        recv = accl.create_buffer(count, dataType.float32)
        send.host[:] = data
        accl.allreduce(send, recv, count, reduceFunction.SUM)
        assert _counter(key) >= before  # plan may already be cached
        np.testing.assert_array_equal(recv.host[0], data.sum(0))
    finally:
        accl.config = saved


def test_world16_4x4_parity_subprocess():
    """The (4, 4) parity leg of the acceptance matrix needs 16 devices —
    more than this process's 9-device emulator — so it runs in a
    subprocess with its own device-count flag: pipelined + sequential
    multiaxis vs XLA psum for all three ops, bit-exact."""
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, numpy as np
        jax.config.update("jax_platforms", "cpu")
        from accl_tpu import Algorithm, dataType, reduceFunction
        from accl_tpu.communicator import Communicator
        from accl_tpu.parallel import algorithms

        comm = Communicator(jax.devices()[:16])
        W, axes = 16, (4, 4)
        rng = np.random.default_rng(0)
        for C in (1, 3):
            x = rng.integers(-100, 100, (W, 36)).astype(np.float32)
            ring = algorithms.build_allreduce(
                comm, reduceFunction.SUM, dataType.float32,
                Algorithm.RING, None)
            ma = algorithms.build_allreduce(
                comm, reduceFunction.SUM, dataType.float32,
                Algorithm.MULTIAXIS, None, mesh_shape=axes,
                pipeline_chunks=C)
            assert np.array_equal(np.asarray(ring(x)), np.asarray(ma(x)))
            xr = rng.integers(-50, 50, (W, 8 * W)).astype(np.int32)
            rs_r = algorithms.build_reduce_scatter(
                comm, reduceFunction.SUM, dataType.int32,
                Algorithm.RING, None)
            rs_m = algorithms.build_reduce_scatter(
                comm, reduceFunction.SUM, dataType.int32,
                Algorithm.MULTIAXIS, None, mesh_shape=axes,
                pipeline_chunks=C)
            assert np.array_equal(np.asarray(rs_r(xr)),
                                  np.asarray(rs_m(xr)))
            xg = rng.standard_normal((W, 9)).astype(np.float32)
            ag_r = algorithms.build_allgather(
                comm, Algorithm.RING, None, dataType.float32)
            ag_m = algorithms.build_allgather(
                comm, Algorithm.MULTIAXIS, None, dataType.float32,
                mesh_shape=axes, pipeline_chunks=C)
            assert np.array_equal(np.asarray(ag_r(xg)),
                                  np.asarray(ag_m(xg)))
        print("OK_4x4")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], timeout=300,
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK_4x4" in r.stdout


# ---------------------------------------------------------------------------
# full authority (sched_full_authority)
# ---------------------------------------------------------------------------

def test_full_authority_off_by_default_pins_equivalence(accl):
    """The flag defaults OFF and the single-axis equivalence pins above
    run under that default — spelled out here so the migration contract
    is its own test."""
    assert ACCLConfig().sched_full_authority is False
    comm = accl.global_comm()
    for nbytes in (64 << 10, 4 << 20, 64 << 20):
        assert algorithms.select(operation.allreduce, nbytes, comm,
                                 accl.config) \
            == algorithms._select_legacy(operation.allreduce, nbytes,
                                         comm, accl.config)


def test_full_authority_retires_ladder_on_single_axis(accl):
    """Flag ON: the per-size-bucket argmin rules the single-axis mesh —
    the kring schedule wins the bandwidth regime (where the ladder said
    RING anyway), the flat star wins the α regime, and seeds no longer
    pin (the ladder they seed is retired)."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_full_authority=True)
    legacy = algorithms._select_legacy(operation.allreduce, 16 << 20,
                                       comm, cfg)
    plan = synth.resolve(operation.allreduce, 16 << 20, comm, cfg, legacy)
    assert plan.source == "full_authority"
    assert plan.shape in ("ring", "kring")
    assert plan.algorithm == Algorithm.RING   # SIM transport: plain ring
    synth.validate_plan(plan)
    # α regime: the 2-hop flat star (the latency tier's pick) falls out
    # of the same argmin — no separate tier needed under full authority
    legacy2 = algorithms._select_legacy(operation.allreduce, 512, comm,
                                        cfg)
    plan2 = synth.resolve(operation.allreduce, 512, comm, cfg, legacy2)
    assert plan2.source == "full_authority" and plan2.shape == "flat"
    # a seeded register does NOT pin under full authority
    seeded = cfg.replace(ring_threshold=64 * 1024)
    legacy3 = algorithms._select_legacy(operation.allreduce, 16 << 20,
                                        comm, seeded)
    plan3 = synth.resolve(operation.allreduce, 16 << 20, comm, seeded,
                          legacy3)
    assert plan3.source == "full_authority"


def test_full_authority_maps_ring_family_to_pallas_on_ici(accl):
    """On real chip links the ring-family shapes execute via the Pallas
    RDMA kernels — the perf core the retired ladder routed large ICI
    payloads to."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_full_authority=True,
                              transport=TransportBackend.ICI)
    legacy = algorithms._select_legacy(operation.allreduce, 16 << 20,
                                       comm, cfg)
    plan = synth.resolve(operation.allreduce, 16 << 20, comm, cfg, legacy)
    if plan.shape in ("ring", "kring"):
        assert plan.algorithm == Algorithm.PALLAS


def test_full_authority_dcn_guard_outranks(accl):
    """The DCN two-tier story outranks even full authority."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_full_authority=True,
                              transport=TransportBackend.DCN)
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       cfg)
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    assert plan.source == "legacy" and plan.algorithm == legacy


def test_full_authority_multiaxis_window(accl):
    """Flag ON on a declared torus: the argmin still lands the
    pipelined multi-axis schedule in the bandwidth window (the full
    candidate space includes it)."""
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_full_authority=True,
                              sched_mesh_shape=[2, 4])
    legacy = algorithms._select_legacy(operation.allreduce, 16 << 20,
                                       comm, cfg)
    plan = synth.resolve(operation.allreduce, 16 << 20, comm, cfg, legacy)
    assert plan.source == "full_authority" and plan.shape == "pipeline"


# ---------------------------------------------------------------------------
# satellites: fingerprint memo, plan-cache stats, --explain CLI
# ---------------------------------------------------------------------------

def test_cost_fingerprint_memoized_per_config():
    """_cost_fingerprint sits on the per-op dispatch path: one tuple
    build per config OBJECT, identity-checked so a recycled id can
    never alias, and new cost fields participate."""
    cfg = ACCLConfig()
    fp1 = synth._cost_fingerprint(cfg)
    assert synth._cost_fingerprint(cfg) is fp1          # memo hit
    cfg2 = cfg.replace(sched_pipeline_chunks=7)
    fp2 = synth._cost_fingerprint(cfg2)
    assert fp2 != fp1                                    # chunks in the key
    assert synth._cost_fingerprint(
        cfg.replace(sched_full_authority=True)) != fp1
    assert synth._cost_fingerprint(
        cfg.replace(sched_pipeline_startup_us=9.0)) != fp1
    # the session hook clears the memo with the plan cache
    synth.reset_plan_cache()
    fp1b = synth._cost_fingerprint(cfg)
    assert fp1b == fp1 and fp1b is not fp1


def test_plan_cache_stats_in_accl_stats(accl):
    """ACCL.stats() surfaces the synth plan cache beside the program
    cache: size, bound, and hit/miss/evict tallies that move with
    resolution traffic."""
    synth.reset_plan_cache()
    comm = accl.global_comm()
    cfg = accl.config.replace(sched_alpha_us=1.0 + 3e-9)
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       cfg)
    synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    st = accl.stats()["sched_plan_cache"]
    assert st["plans"] >= 1 and st["max_size"] == synth._PLAN_CACHE_MAX
    assert st["hits"] >= 1 and st["misses"] >= 1
    assert st["evictions"] == 0
    import json
    json.dumps(st)  # stats() stays JSON-round-trippable


def test_plan_cache_evicts_at_bound(monkeypatch):
    """The LRU bound evicts the oldest plan and counts it."""
    synth.reset_plan_cache()
    monkeypatch.setattr(synth, "_PLAN_CACHE_MAX", 2)
    comm = _FakeComm([object()] * 8)
    cfg = ACCLConfig(transport=TransportBackend.SIM)
    e0 = _counter('accl_sched_plan_cache_total{event="evict"}')
    for i in range(3):  # distinct cost params -> three distinct keys
        synth.resolve(operation.allreduce, 9 << 20, comm,
                      cfg.replace(sched_alpha_us=1.0 + (i + 1) * 1e-9),
                      Algorithm.RING)
    st = synth.plan_cache_stats()
    assert st["plans"] == 2 and st["evictions"] == 1
    assert _counter('accl_sched_plan_cache_total{event="evict"}') == e0 + 1
    synth.reset_plan_cache()


def test_synth_explain_cli_smoke():
    """`python -m accl_tpu.parallel.synth --explain OP NBYTES SHAPE`
    prints the candidate table (cost breakdown, winner, resolve()
    decision) for a hypothetical topology — no live session needed."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "accl_tpu.parallel.synth", "--explain",
         "allreduce", str(8 << 20), "2x4"],
        timeout=180, capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    out = r.stdout
    assert "winner" in out and "pipeline" in out and "multiaxis" in out
    assert "resolve() decision" in out and "source=cost_model" in out
    # unknown op fails fast with the menu
    r2 = subprocess.run(
        [sys.executable, "-m", "accl_tpu.parallel.synth", "--explain",
         "bogus", "1024", "2x4"],
        timeout=180, capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r2.returncode != 0


def test_plan_cache_hit_refreshes_recency(monkeypatch):
    """LRU, not FIFO: a hit re-inserts the plan, so the hot first-resolved
    plan survives the bound while the cold untouched one evicts."""
    synth.reset_plan_cache()
    monkeypatch.setattr(synth, "_PLAN_CACHE_MAX", 2)
    comm = _FakeComm([object()] * 8)
    base = ACCLConfig(transport=TransportBackend.SIM)
    cfgs = [base.replace(sched_alpha_us=1.0 + (i + 1) * 1e-9)
            for i in range(3)]
    hot = synth.resolve(operation.allreduce, 9 << 20, comm, cfgs[0],
                        Algorithm.RING)
    synth.resolve(operation.allreduce, 9 << 20, comm, cfgs[1],
                  Algorithm.RING)
    # touch the hot plan: it must now outlive the bound...
    assert synth.resolve(operation.allreduce, 9 << 20, comm, cfgs[0],
                         Algorithm.RING) is hot
    synth.resolve(operation.allreduce, 9 << 20, comm, cfgs[2],
                  Algorithm.RING)   # evicts cfgs[1]'s plan, not hot's
    m0 = synth.plan_cache_stats()["misses"]
    assert synth.resolve(operation.allreduce, 9 << 20, comm, cfgs[0],
                         Algorithm.RING) is hot
    assert synth.plan_cache_stats()["misses"] == m0   # still cached
    synth.reset_plan_cache()


# ---------------------------------------------------------------------------
# two-tier DCN schedules (ISSUE 15): per-tier cost model, resolution
# window, parity suite, equivalence pins
# ---------------------------------------------------------------------------

def _host_aligned(monkeypatch, comm, shape=(2, 4)):
    monkeypatch.setattr(type(comm), "hosts_shape", lambda self: shape)


@pytest.mark.parametrize("op", synth.SYNTH_OPS)
@pytest.mark.parametrize("wire,ratio", [("off", 1.0), ("bf16", 0.5)])
def test_twotier_candidates_validate(accl, op, wire, ratio):
    """Every two-tier candidate passes the ownership algebra — including
    the decompress-fold exchange step (1 DCN hop, per-slice coverage)
    — for compressed and full-precision arms at multiple sizes."""
    cfg = accl.config.replace(transport=TransportBackend.DCN,
                              dcn_wire_dtype=wire)
    for axes in ((2, 4), (4, 2)):
        topo = synth.Topology(axes, TransportBackend.DCN, True, dcn_axis=0)
        model = synth.model_for(cfg, topo)
        for nbytes in (4 << 10, 4 << 20):
            N = synth._payload_total(op, nbytes, topo.world)
            plan = synth._gen_twotier(op, topo, N, model, wire, ratio)
            assert plan is not None and plan.shape == "twotier"
            synth.validate_plan(plan)
            assert plan.param("dcn_wire_dtype") == wire


def test_twotier_cost_per_tier_pinned(accl):
    """THE per-tier pricing pin: a two-tier plan's predicted cost uses
    the DCN α/β pair for the cross-slice step ONLY and the ICI pair for
    the intra-slice steps — exact to the unit, for all three ops."""
    cfg = accl.config.replace(transport=TransportBackend.DCN,
                              dcn_wire_dtype="bf16")
    topo = synth.Topology((2, 4), TransportBackend.DCN, True, dcn_axis=0)
    model = synth.model_for(cfg, topo)
    ici = synth.CostModel.from_config(cfg, TransportBackend.ICI)
    dcn = synth.CostModel.from_config(cfg, TransportBackend.DCN)
    assert (ici.alpha_us, ici.beta_gbps) == (cfg.sched_alpha_us,
                                             cfg.sched_beta_gbps)
    assert (dcn.alpha_us, dcn.beta_gbps) == (cfg.sched_dcn_alpha_us,
                                             cfg.sched_dcn_beta_gbps)
    S, L, k, r = 2, 4, 2, 0.5
    N = 8 << 20
    ici_leg = ici.step_us(L - 1, N * (L - 1) / L, k)
    pins = {
        operation.allreduce:
            2 * ici_leg + dcn.step_us(1, (N / L) * (S - 1) * r, 1),
        operation.reduce_scatter:
            ici_leg + dcn.step_us(1, (N / L) * (S - 1) / S * r, 1),
        operation.allgather:
            ici_leg + dcn.step_us(1, (N / (S * L)) * (S - 1) * r, 1),
    }
    for op, want in pins.items():
        plan = synth._gen_twotier(op, topo, N, model, "bf16", r)
        assert plan.predicted_us == pytest.approx(want, abs=1e-9)
        # the step transports themselves are marked per tier
        dcn_steps = [s for s in plan.steps
                     if s.transport == TransportBackend.DCN]
        ici_steps = [s for s in plan.steps
                     if s.transport == TransportBackend.ICI]
        assert len(dcn_steps) == 1 and dcn_steps[0].axis == 0
        assert all(s.axis == 1 for s in ici_steps)
        assert len(dcn_steps) + len(ici_steps) == len(plan.steps)


def test_resolve_twotier_on_host_aligned_dcn(accl, monkeypatch):
    """THE acceptance pin: with ``dcn_wire_dtype`` set, resolution on a
    host-aligned multi-slice DCN topology picks the COMPRESSED two-tier
    schedule at large payloads, counted under accl_sched_plan_total."""
    comm = accl.global_comm()
    _host_aligned(monkeypatch, comm)
    cfg = accl.config.replace(transport=TransportBackend.DCN,
                              dcn_wire_dtype="bf16",
                              sched_alpha_us=1.0 + 5e-9)  # fresh keys
    key = ('accl_sched_plan_total{op="allreduce",shape="twotier",'
           'source="cost_model"}')
    before = _counter(key)
    for nbytes in (1 << 20, 8 << 20, 64 << 20):
        assert algorithms.select(operation.allreduce, nbytes, comm, cfg) \
            == Algorithm.TWOTIER
    assert _counter(key) > before
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       cfg)
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy)
    assert plan.shape == "twotier" and plan.source == "cost_model"
    assert plan.param("dcn_wire_dtype") == "bf16"  # the COMPRESSED arm
    assert plan.param("shape2d") == (2, 4)
    synth.validate_plan(plan)
    # the duals ride the window too (per-op byte conventions)
    assert algorithms.select(operation.allgather, 4 << 20, comm, cfg) \
        == Algorithm.TWOTIER
    assert algorithms.select(operation.reduce_scatter, 32 << 20, comm,
                             cfg) == Algorithm.TWOTIER


def test_dcn_wire_off_resolution_byte_identical(accl, monkeypatch):
    """The "off" contract (equivalence pin): with the default
    ``dcn_wire_dtype="off"`` EVERY DCN resolution — host-aligned or not
    — is byte-identical to the legacy scalar ladder, exactly as before
    the two-tier refactor."""
    comm = accl.global_comm()
    _host_aligned(monkeypatch, comm)
    cfg = accl.config.replace(transport=TransportBackend.DCN)
    assert cfg.dcn_wire_dtype == "off"
    for op in synth.SYNTH_OPS:
        for nbytes in (1024, 64 << 10, 4 << 20, 64 << 20):
            got = algorithms.select(op, nbytes, comm, cfg)
            assert got == algorithms._select_legacy(op, nbytes, comm, cfg)
            legacy = algorithms._select_legacy(op, nbytes, comm, cfg)
            plan = synth.resolve(op, nbytes, comm, cfg, legacy)
            assert plan.source == "legacy" and plan.algorithm == legacy


def test_single_slice_resolution_ignores_dcn_wire(accl):
    """The wire register must not perturb single-slice resolution: SIM
    and ICI decisions are identical with and without it (the register
    is in the cost fingerprint, so this is a behavior pin, not a
    caching accident)."""
    comm = accl.global_comm()
    for transport in (TransportBackend.SIM, TransportBackend.ICI):
        base = accl.config.replace(transport=transport)
        wired = base.replace(dcn_wire_dtype="bf16")
        for op in synth.SYNTH_OPS:
            for nbytes in (1024, 4 << 20, 64 << 20):
                assert algorithms.select(op, nbytes, comm, base) \
                    == algorithms.select(op, nbytes, comm, wired)


def test_twotier_seeds_pin_baseline_not_window(accl, monkeypatch):
    """Seed semantics in the two-tier window: the wire register is
    ITSELF a non-default opt-in and outranks generic autotune seeds —
    a seeded ladder pins the BASELINE the two-tier candidates must
    strictly beat, never the window (otherwise autotune_session's own
    threshold stages would make its dcn_twotier go/no-go unreachable
    in the very config it produces). With the wire OFF, seeds keep the
    full pre-refactor pinning."""
    comm = accl.global_comm()
    _host_aligned(monkeypatch, comm)
    seeded = accl.config.replace(transport=TransportBackend.DCN,
                                 dcn_wire_dtype="bf16",
                                 dcn_hier_threshold=128 * 1024,
                                 ring_threshold=2 << 20)
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       seeded)
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, seeded,
                         legacy)
    # the window opened: the compressed two-tier schedule beat the
    # seeded ladder's baseline on the per-tier model
    assert plan.shape == "twotier" and plan.source == "cost_model"
    # wire off + seeds: byte-identical to the ladder (the tuned
    # deployment that never opted in stays exactly pre-refactor)
    off = seeded.replace(dcn_wire_dtype="off")
    for nbytes in (64 << 10, 8 << 20):
        got = algorithms.select(operation.allreduce, nbytes, comm, off)
        assert got == algorithms._select_legacy(operation.allreduce,
                                                nbytes, comm, off)


def test_twotier_window_closed_for_inert_wires(accl, rng, monkeypatch):
    """A call the cross-slice codec cannot actually compress — an
    ArithConfig wire already narrowing every hop, or an INTEGER payload
    the codec refuses — keeps the legacy resolution (the builders stand
    the per-leg codec down there; pricing/counting it would describe an
    exchange that never runs), and no DCN wire bytes are accounted."""
    comm = accl.global_comm()
    _host_aligned(monkeypatch, comm)
    cfg = accl.config.replace(transport=TransportBackend.DCN,
                              dcn_wire_dtype="bf16")
    legacy = algorithms._select_legacy(operation.allreduce, 8 << 20, comm,
                                       cfg)
    t0 = synth.dcn_wire_totals()
    plan = synth.resolve(operation.allreduce, 8 << 20, comm, cfg, legacy,
                         count=2 << 20, wire_inert=True)
    assert plan.source == "legacy" and plan.shape != "twotier"
    # AUTO int32 at a window payload: the spec layer marks the wire
    # inert from the dtype, so the phantom-compressed candidate never
    # prices in and the legacy program dispatches (exact)
    count32 = 1 << 20
    idata = rng.integers(-50, 50, (WORLD, count32)).astype(np.int32)
    saved = accl.config
    accl.config = cfg
    try:
        si = accl.create_buffer(count32, dataType.int32)
        ri = accl.create_buffer(count32, dataType.int32)
        si.host[:] = idata
        accl.allreduce(si, ri, count32, reduceFunction.SUM)
        np.testing.assert_array_equal(ri.host[0], idata.sum(0))
    finally:
        accl.config = saved
    assert synth.dcn_wire_totals() == t0  # ints never falsely accounted
    # ...and the full e2e path: a compress_dtype call on the DCN
    # session dispatches the legacy program, correctly
    count = 1 << 10
    data = rng.integers(-50, 50, (WORLD, count)).astype(np.float32)
    saved = accl.config
    accl.config = cfg
    try:
        send = accl.create_buffer(count, dataType.float32)
        recv = accl.create_buffer(count, dataType.float32)
        send.host[:] = data
        accl.allreduce(send, recv, count, reduceFunction.SUM,
                       compress_dtype=dataType.bfloat16)
        np.testing.assert_allclose(recv.host[0],
                                   data.astype(np.float64).sum(0),
                                   rtol=0.1, atol=2.0)
    finally:
        accl.config = saved
    assert synth.dcn_wire_totals() == t0  # nothing falsely accounted


def test_twotier_decline_counted_without_host_shape(accl):
    """A dcn_wire_dtype request on a DCN mesh with NO slice boundary
    declines visibly (once per synthesized plan) instead of silently
    resolving legacy."""
    comm = accl.global_comm()
    assert comm.hosts_shape() is None
    cfg = accl.config.replace(transport=TransportBackend.DCN,
                              dcn_wire_dtype="bf16",
                              sched_alpha_us=1.0 + 7e-9)  # fresh keys
    key = ('accl_select_decline_total{op="allgather",'
           'reason="dcn_no_host_shape"}')
    before = _counter(key)
    got = algorithms.select(operation.allgather, 8 << 20, comm, cfg)
    assert got != Algorithm.TWOTIER
    assert _counter(key) - before == 1.0
    # cached second resolution does not re-count (per plan, not per call)
    algorithms.select(operation.allgather, 8 << 20, comm, cfg)
    assert _counter(key) - before == 1.0


def test_twotier_wire_bytes_counted(accl, monkeypatch):
    """Each dispatch resolution of a two-tier plan accounts the
    cross-slice leg pre/post compression —
    accl_dcn_wire_bytes_total{op,dtype,stage} and the stats() totals."""
    comm = accl.global_comm()
    _host_aligned(monkeypatch, comm)
    cfg = accl.config.replace(transport=TransportBackend.DCN,
                              dcn_wire_dtype="bf16")
    pre_k = ('accl_dcn_wire_bytes_total{op="allreduce",dtype="bf16",'
             'stage="pre"}')
    post_k = ('accl_dcn_wire_bytes_total{op="allreduce",dtype="bf16",'
              'stage="post"}')
    p0, q0 = _counter(pre_k), _counter(post_k)
    t0 = synth.dcn_wire_totals()
    nbytes = 8 << 20
    algorithms.select_plan(operation.allreduce, nbytes, comm, cfg,
                           count=nbytes // 4)
    # allreduce on (2,4): the DCN leg carries (N/4)*(2-1) pre bytes,
    # half that at bf16
    want_pre = (nbytes / 4) * 1
    assert _counter(pre_k) - p0 == pytest.approx(want_pre)
    assert _counter(post_k) - q0 == pytest.approx(want_pre / 2)
    t1 = synth.dcn_wire_totals()
    assert t1["pre_bytes"] - t0["pre_bytes"] == pytest.approx(want_pre)
    assert t1["post_bytes"] - t0["post_bytes"] \
        == pytest.approx(want_pre / 2)


# -- program layer: two-tier parity --------------------------------------

@pytest.mark.parametrize("count", [64, 100])  # incl. the padding path
def test_twotier_allreduce_bit_exact(accl, rng, count):
    """dcn_wire_dtype="off" (the default) is BIT-exact against the flat
    baselines — integer-valued operands, padding path included."""
    dt = dataType.float32
    data = rng.integers(-100, 100, (WORLD, count)).astype(np.float32)
    outs = {}
    for algo in (Algorithm.RING, Algorithm.XLA, Algorithm.TWOTIER):
        send = accl.create_buffer(count, dt)
        recv = accl.create_buffer(count, dt)
        send.host[:] = data
        accl.allreduce(send, recv, count, reduceFunction.SUM,
                       algorithm=algo)
        outs[algo] = recv.host.copy()
    np.testing.assert_array_equal(outs[Algorithm.TWOTIER],
                                  outs[Algorithm.RING])
    np.testing.assert_array_equal(outs[Algorithm.TWOTIER],
                                  outs[Algorithm.XLA])
    np.testing.assert_array_equal(outs[Algorithm.TWOTIER][0], data.sum(0))


def test_twotier_allreduce_max(accl, rng):
    """MAX rides the general decompress-fold path (a non-sum fold must
    decompress before folding); int32 payloads never compress."""
    count, dt = 48, dataType.int32
    data = rng.integers(-100, 100, (WORLD, count)).astype(np.int32)
    for wire in (None, "bf16"):
        saved = accl.config
        if wire:
            accl.config = saved.replace(dcn_wire_dtype=wire)
        try:
            send = accl.create_buffer(count, dt)
            recv = accl.create_buffer(count, dt)
            send.host[:] = data
            accl.allreduce(send, recv, count, reduceFunction.MAX,
                           algorithm=Algorithm.TWOTIER)
            for r in range(WORLD):
                np.testing.assert_array_equal(recv.host[r], data.max(0))
        finally:
            accl.config = saved


def test_twotier_reduce_scatter_bit_exact(accl, rng):
    """Chunk realignment: rank (i, j) of the (slices, per_slice) mesh
    must land FLAT chunk i*L+j — bit-identical to the 1-D ring path."""
    count, dt = 48, dataType.int32
    data = rng.integers(-50, 50, (WORLD, count * WORLD)).astype(np.int32)
    outs = {}
    for algo in (Algorithm.RING, Algorithm.TWOTIER):
        send = accl.create_buffer(count * WORLD, dt)
        recv = accl.create_buffer(count, dt)
        send.host[:] = data
        accl.reduce_scatter(send, recv, count, reduceFunction.SUM,
                            algorithm=algo)
        outs[algo] = recv.host.copy()
    np.testing.assert_array_equal(outs[Algorithm.TWOTIER],
                                  outs[Algorithm.RING])
    for r in range(WORLD):
        np.testing.assert_array_equal(
            outs[Algorithm.TWOTIER][r],
            data[:, r * count:(r + 1) * count].sum(0))


def test_twotier_allgather_bit_exact(accl, rng):
    count, dt = 33, dataType.float32
    data = rng.standard_normal((WORLD, count)).astype(np.float32)
    outs = {}
    for algo in (Algorithm.RING, Algorithm.TWOTIER):
        send = accl.create_buffer(count, dt)
        recv = accl.create_buffer(count * WORLD, dt)
        send.host[:] = data
        accl.allgather(send, recv, count, algorithm=algo)
        outs[algo] = recv.host.copy()
    np.testing.assert_array_equal(outs[Algorithm.TWOTIER],
                                  outs[Algorithm.RING])
    for r in range(WORLD):
        np.testing.assert_array_equal(outs[Algorithm.TWOTIER][r],
                                      data.reshape(-1))


@pytest.mark.parametrize("wire", ["bf16", "bf16_sr"])
def test_twotier_wire_tolerance(accl, rng, wire):
    """Compressed cross-slice legs are tolerance-bounded: the shard
    crosses the DCN once in bf16 (~2^-8 relative), every fold runs at
    full precision after decompression. bf16_sr degrades to the
    deterministic cast off-TPU — same bound either way."""
    count, dt = 96, dataType.float32
    data = (rng.standard_normal((WORLD, count)) * 100).astype(np.float32)
    saved = accl.config
    accl.config = saved.replace(dcn_wire_dtype=wire)
    try:
        send = accl.create_buffer(count, dt)
        recv = accl.create_buffer(count, dt)
        send.host[:] = data
        accl.allreduce(send, recv, count, reduceFunction.SUM,
                       algorithm=Algorithm.TWOTIER)
        expect = data.astype(np.float64).sum(0)
        for r in range(WORLD):
            np.testing.assert_allclose(recv.host[r], expect,
                                       rtol=0.02, atol=3.0)
        # the duals: allgather's DCN leg rounds each block once
        send2 = accl.create_buffer(count, dt)
        recv2 = accl.create_buffer(count * WORLD, dt)
        send2.host[:] = data
        accl.allgather(send2, recv2, count, algorithm=Algorithm.TWOTIER)
        np.testing.assert_allclose(
            recv2.host[0].reshape(WORLD, count), data, rtol=0.01,
            atol=0.5)
    finally:
        accl.config = saved


def test_twotier_auto_dispatch_end_to_end(accl, rng, monkeypatch):
    """AUTO on a host-aligned DCN session with the wire register set:
    the call dispatches the two-tier schedule (selection counter) and
    the result lands within the bf16 tolerance class."""
    comm = accl.global_comm()
    _host_aligned(monkeypatch, comm)
    count = 1 << 20  # 4 MiB f32 — deep in the two-tier window
    dt = dataType.float32
    saved = accl.config
    accl.config = saved.replace(transport=TransportBackend.DCN,
                                dcn_wire_dtype="bf16")
    try:
        key = ('accl_algorithm_selected_total{op="allreduce",'
               'algorithm="twotier"}')
        before = _counter(key)
        data = rng.integers(-8, 8, (WORLD, count)).astype(np.float32)
        send = accl.create_buffer(count, dt)
        recv = accl.create_buffer(count, dt)
        send.host[:] = data
        accl.allreduce(send, recv, count, reduceFunction.SUM)
        assert _counter(key) > before
        np.testing.assert_allclose(recv.host[0],
                                   data.astype(np.float64).sum(0),
                                   rtol=0.02, atol=2.0)
    finally:
        accl.config = saved


def test_twotier_explicit_needs_composite_world(accl):
    comm = accl.global_comm().split(range(7))
    with pytest.raises(ValueError, match="composite world"):
        algorithms.build_allreduce(comm, reduceFunction.SUM,
                                   dataType.float32, Algorithm.TWOTIER,
                                   None)


def test_dcn_wire_dtype_write_through_and_validation(accl):
    """The config setter writes the register through to the
    hierarchical session default; a typo fails loudly."""
    from accl_tpu.parallel import hierarchical
    saved = accl.config
    try:
        accl.config = saved.replace(dcn_wire_dtype="bf16_sr")
        assert hierarchical.get_dcn_wire_dtype() == "bf16_sr"
        with pytest.raises(ValueError, match="dcn_wire_dtype"):
            accl.config = saved.replace(dcn_wire_dtype="fp8")
    finally:
        accl.config = saved
        assert hierarchical.get_dcn_wire_dtype() == "off"


def test_synth_explain_cli_dcn_smoke(capsys):
    """--explain on a DCN topology prints the per-tier cost split and
    the twotier candidates."""
    rc = synth._main(["--explain", "allreduce", str(8 << 20), "2x4",
                      "--transport", "dcn"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "twotier/off" in out
    assert "per-tier split" in out and "dcn=" in out and "ici=" in out
    assert "dcn_axis=0" in out


def test_cmdlist_twotier_one_launch_and_reresolution(accl, rng,
                                                     monkeypatch):
    """A two-tier schedule recorded in a CommandList compiles into the
    ONE-launch composite, and execute()-time re-resolution picks up the
    wire register: the same recorded list dispatches the compressed
    schedule once the session config flips dcn_wire_dtype on."""
    comm = accl.global_comm()
    _host_aligned(monkeypatch, comm)
    count, dt = 64, dataType.float32
    data = rng.integers(-100, 100, (WORLD, count)).astype(np.float32)
    send = accl.create_buffer(count, dt)
    recv = accl.create_buffer(count, dt)
    send.host[:] = data
    key = 'accl_cmdlist_executes_total{steps="2"}'
    before = _counter(key)
    cl = accl.command_list()
    cl.allreduce(send, recv, count, reduceFunction.SUM,
                 algorithm=Algorithm.TWOTIER)
    cl.allgather(recv, accl.create_buffer(count * WORLD, dt), count,
                 algorithm=Algorithm.TWOTIER)
    cl.execute()
    assert _counter(key) == before + 1
    np.testing.assert_array_equal(recv.host[0], data.sum(0))
    # flip the wire register and re-execute the SAME list: the
    # re-resolution keys a fresh program (compressed leg) and the
    # result moves to the bf16 tolerance class, still correct
    saved = accl.config
    accl.config = saved.replace(dcn_wire_dtype="bf16")
    try:
        send.host[:] = data
        cl.execute()
        np.testing.assert_allclose(recv.host[0],
                                   data.astype(np.float64).sum(0),
                                   rtol=0.02, atol=2.0)
    finally:
        accl.config = saved
