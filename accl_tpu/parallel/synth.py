"""Topology-aware schedule synthesis — cost-model search over the torus.

``select()`` used to be a pile of ~15 hand-tuned scalar byte thresholds,
and every bandwidth algorithm ran on ONE flat logical ring in rank order
— which ignores that a v5e 2x4 (and any multi-pod slice) is a torus with
independent link budgets per axis. This module replaces the guesswork
for the bandwidth collectives (allreduce / allgather / reduce_scatter)
with schedule *synthesis* in the style of "Synthesizing Optimal
Collective Algorithms" (arxiv 2008.08708):

* an **α-β cost model** per (op, topology, payload bytes, wire dtype):
  each schedule step costs ``α·hops + link_bytes/(channels·β)`` where
  ``link_bytes`` is the traffic through the *busiest link* of that step
  and ``channels`` counts concurrently driven link directions
  (counter-rotating rings double them);
* **candidate generators** covering the whole historical family — flat
  star, binary tree, single ring, k-concurrent counter-rotating rings,
  the two-tier hierarchical split — plus the **multi-axis torus
  decomposition** (axis-by-axis reduce-scatter → all-gather, the
  closed-form-optimal shape of "Near-Optimal Wafer-Scale Reduce",
  arxiv 2404.15888), which drives BOTH torus axes instead of one
  logical ring and strictly lowers both the hop count (Σ(sᵢ−1) vs P−1
  per leg) and the busiest-link bytes (the heavy leg moves
  N·(s₀−1)/s₀ < N·(P−1)/P);
* a :class:`SchedulePlan` object that :func:`resolve` synthesizes per
  (op, topology, size-bucket) and caches — ONE plan object instead of N
  scalars.  The legacy scalar thresholds are honored as **explicit
  overrides**: a register that differs from its dataclass default (an
  autotune seed or an operator's hand tune) pins the legacy decision
  for the ops it governs, so existing tuned deployments keep resolving
  exactly as before.

Winning multi-step schedules compile into ONE cached XLA program (the
:mod:`accl_tpu.cmdlist` "one launch per sequence" discipline): the
multi-axis builders below trace every phase into a single ``shard_map``
program over the communicator's 2-D mesh, so a whole synthesized
collective launches as one unit and caches in the ProgramCache /
CommandList composite like any other per-op program.

Every candidate a generator emits is checkable: :func:`validate_plan`
runs an ownership algebra over the step DAG proving each (chunk, rank)
is covered exactly once, the dependencies are acyclic, and the per-axis
hop counts match what the cost model charged.  See
``docs/scheduling.md`` for the full model and migration story.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from ..config import ACCLConfig, Algorithm, TransportBackend
from ..constants import dataType, operation, reduceFunction
from ..obs import metrics as _metrics

#: ops the synthesizer owns — the bandwidth collectives whose payload
#: admits a chunk decomposition. Everything else keeps the legacy ladder.
SYNTH_OPS = (operation.allreduce, operation.allgather,
             operation.reduce_scatter)

#: candidate shape names (the ``shape`` label of the plan counters) —
#: ``pipeline`` is the chunk-pipelined multi-axis schedule (same
#: Algorithm.MULTIAXIS builders, payload split into
#: ``sched_pipeline_chunks`` chunks whose per-axis legs overlap);
#: ``twotier`` is the DCN two-tier schedule (intra-slice reduce-scatter
#: → cross-slice exchange, optionally compressed to
#: ``cfg.dcn_wire_dtype`` — → intra-slice all-gather)
SHAPES = ("xla", "flat", "tree", "ring", "kring", "multiaxis", "pipeline",
          "hier", "twotier")

#: effective wire itemsize of each DCN cross-slice wire dtype
#: (``ACCLConfig.dcn_wire_dtype``); "off" compresses nothing
DCN_WIRE_ITEMSIZE = {"bf16": 2, "bf16_sr": 2}


def dcn_wire_bytes(nbytes: int, wire: Optional[str],
                   count: Optional[int] = None) -> int:
    """Effective cross-slice bytes for a payload of ``nbytes`` under the
    DCN wire dtype — the ``algorithms.cmatmul_wire_bytes`` discipline:
    ``count`` (elements) resolves the operand width exactly, without it
    the f32 default is assumed, and the wire never upcasts (operands at
    or below the wire width move unchanged)."""
    wisz = DCN_WIRE_ITEMSIZE.get(wire or "off")
    if wisz is None:
        return nbytes
    op_isz = (nbytes // count) if count else 4
    if op_isz <= wisz or op_isz <= 0:
        return nbytes
    return (nbytes // op_isz) * wisz


def _prod(axes) -> int:
    p = 1
    for s in axes:
        p *= int(s)
    return p


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Topology:
    """What the synthesizer knows about the mesh: per-axis sizes (product
    == world; a single entry means "no torus structure known"), the
    transport the links ride, and whether both link directions are
    drivable concurrently (counter-rotating rings).  ``dcn_axis`` marks
    the axis whose links cross slices over DCN (the host boundary of a
    multi-slice mesh, from ``Communicator.hosts_shape``): steps on that
    axis are priced with the DCN α/β pair, every other axis rides
    intra-slice ICI — the per-tier pricing a two-tier schedule needs
    (one transport pricing a mixed plan would misprice it by
    construction)."""

    axes: Tuple[int, ...]
    transport: TransportBackend
    bidirectional: bool
    dcn_axis: Optional[int] = None

    @property
    def world(self) -> int:
        p = 1
        for s in self.axes:
            p *= s
        return p

    @property
    def multi_axis(self) -> bool:
        return len(self.axes) >= 2


def _coords_shape(devices) -> Optional[Tuple[int, int]]:
    """(rows, cols) from TPU chip coordinates when the devices form a
    full rectangular grid with >1 extent on exactly the x and one other
    axis; None otherwise (CPU emulator devices carry no coords). cols is
    the x extent — the fastest-varying coordinate under snake rank
    order, so ``mesh2d(rows, cols)`` rows are physical x-runs."""
    coords = []
    for d in devices:
        c = getattr(d, "coords", None)
        if c is None:
            return None
        coords.append((tuple(c) + (0, 0, 0))[:3])
    if len(set(coords)) != len(coords):
        return None  # multiple cores per chip: grid accounting is off
    ext = [len({c[i] for c in coords}) for i in range(3)]
    if ext[0] * ext[1] * ext[2] != len(coords):
        return None  # not a full rectangular grid
    if ext[0] < 2 or sum(1 for e in ext if e > 1) != 2:
        # a 3-D slice (e.g. v4 2x2x2) has no single second axis whose
        # rings are physical links — collapsing y·z into "rows" would
        # break the cost model's independent-link-budget premise
        return None
    cols = ext[0]
    rows = len(coords) // cols
    return (rows, cols)


def _coords_degraded(devices) -> bool:
    """True when every device carries unique chip coordinates but they do
    NOT fill a rectangular grid — the survivor-subset signature (a rank
    died and the mesh shrank around the hole). Distinct from the benign
    Nones of :func:`_coords_shape`: no coords (CPU emulator), duplicate
    cores, 1-D lines and 3-D slices are legitimate single-axis verdicts,
    a HOLED grid is a degraded one (counted by :func:`resolve` so the
    lost multi-axis schedule is attributable, never invisible)."""
    coords = []
    for d in devices:
        c = getattr(d, "coords", None)
        if c is None:
            return False
        coords.append((tuple(c) + (0, 0, 0))[:3])
    if len(set(coords)) != len(coords):
        return False
    ext = [len({c[i] for c in coords}) for i in range(3)]
    return ext[0] * ext[1] * ext[2] != len(coords)


def degraded_reason(comm, cfg: ACCLConfig) -> Optional[str]:
    """Why this communicator LOST torus structure, or None when it never
    had any to lose. Fires only for communicators a shrink recovery
    built (``comm.degraded_from`` carries the pre-death world size) — an
    ordinary sub-communicator routinely mismatches the global
    ``sched_mesh_shape`` declaration and may sit on a partial coordinate
    grid without anything being wrong, and counting those as
    degradations would make a real shrink indistinguishable from group
    creation. ``declared_shape_mismatch``: the declared shape describes
    the pre-death world; ``holed_grid``: the survivors' device
    coordinates no longer fill a rectangular grid. Either way the honest
    resolution is the single-axis logical ring over the survivors —
    never an invented multi-axis decomposition over missing links
    (which holds for ALL single-axis verdicts, marked or not)."""
    if getattr(comm, "degraded_from", None) is None:
        return None
    ms = cfg.sched_mesh_shape
    if ms and _prod(ms) != comm.world_size:
        return "declared_shape_mismatch"
    if _coords_degraded(getattr(comm, "_devices", None) or comm.devices):
        return "holed_grid"
    return None


_COORDS_UNSET = object()


def _coords_shape_cached(comm) -> Optional[Tuple[int, int]]:
    """Per-communicator memo of :func:`_coords_shape` — the device list
    is immutable after construction and the scan is O(world), but
    ``resolve()`` runs on the per-op host dispatch path."""
    cached = getattr(comm, "_synth_coords_shape", _COORDS_UNSET)
    if cached is _COORDS_UNSET:
        cached = _coords_shape(getattr(comm, "_devices", None)
                               or comm.devices)
        try:
            comm._synth_coords_shape = cached
        except AttributeError:
            pass  # exotic comm without a writable __dict__: just rescan
    return cached


def torus_shape(comm, cfg: ACCLConfig,
                allow_factor2d: bool = False) -> Optional[Tuple[int, ...]]:
    """The torus factorization the multi-axis builders run on — an axes
    tuple of ANY rank >= 2: an explicit ``cfg.sched_mesh_shape`` wins
    (the emulated-topology declaration; a DECLARED ``[2, 2, 2]``
    dispatches a real 3-axis decomposition), else the device-coordinate
    grid (2-D only: :func:`_coords_shape` refuses to infer a second
    axis from a 3-D slice), else — only for EXPLICIT
    ``Algorithm.MULTIAXIS`` requests (``allow_factor2d``) — the
    most-square factorization, mirroring ``_hier_shape``'s fallback.
    AUTO never invents a torus: with neither declaration nor coords the
    mesh is treated as single-axis and the legacy ladder stands."""
    ms = cfg.sched_mesh_shape
    if ms:
        axes = tuple(int(s) for s in ms)
        if len(axes) < 2 or any(s < 2 for s in axes):
            raise ValueError(
                f"sched_mesh_shape needs >=2 axes of extent >=2, got "
                f"{list(axes)}")
        if _prod(axes) == comm.world_size:
            return axes
        if getattr(comm, "parent", None) is None:
            # the declaration targets this (top-level) comm and is wrong:
            # fail loudly rather than silently running single-axis
            raise ValueError(
                f"sched_mesh_shape {'x'.join(map(str, axes))} != world "
                f"{comm.world_size}")
        # a sub-communicator: the declaration describes the GLOBAL mesh,
        # not this group — fall through to coords / single-axis
    shape = _coords_shape_cached(comm)
    if shape is not None:
        return shape
    if allow_factor2d:
        from .hierarchical import factor2d
        return factor2d(comm.world_size)
    return None


def topology_of(comm, cfg: ACCLConfig) -> Topology:
    """Resolve the mesh's :class:`Topology` for plan synthesis.

    On a DCN transport the two-tier split comes from the PHYSICAL slice
    boundary — ``comm.hosts_shape()`` (slices, per-slice), axis 0
    marked as the DCN axis — never from a declared ``sched_mesh_shape``
    (declarations describe ICI tori; inventing a slice boundary would
    put the bandwidth-heavy intra-slice legs on DCN links, the ADVICE
    r2 #4 trap). A non-host-aligned DCN mesh stays single-axis."""
    transport = cfg.transport or TransportBackend.SIM
    if transport == TransportBackend.DCN:
        hs = comm.hosts_shape()
        if hs is not None:
            return Topology(axes=tuple(hs), transport=transport,
                            bidirectional=bool(cfg.bidirectional_rings),
                            dcn_axis=0)
        return Topology(axes=(comm.world_size,), transport=transport,
                        bidirectional=bool(cfg.bidirectional_rings))
    shape = torus_shape(comm, cfg)
    axes = tuple(shape) if shape is not None else (comm.world_size,)
    return Topology(axes=axes, transport=transport,
                    bidirectional=bool(cfg.bidirectional_rings))


# ---------------------------------------------------------------------------
# α-β cost model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CostModel:
    """Per-transport α-β parameters: ``alpha_us`` is one hop's fixed
    latency (launch + link), ``beta_gbps`` one link direction's
    bandwidth. Seeded from config (autotune calibrates them on the live
    mesh — ``bench.autotune_sched_synth``; the DCN pair by
    ``bench.autotune_dcn_twotier``).

    A TIERED model (:meth:`tiered`) additionally carries the DCN α/β
    pair so each step is priced by its OWN transport
    (``step_us(..., transport=)``): on a two-tier multi-slice topology
    the intra-slice steps ride the default (ICI) parameters and the
    cross-slice steps the DCN pair — one transport pricing every step
    of a mixed plan would misprice it by construction."""

    alpha_us: float
    beta_gbps: float
    dcn_alpha_us: Optional[float] = None
    dcn_beta_gbps: Optional[float] = None

    @classmethod
    def from_config(cls, cfg: ACCLConfig,
                    transport: TransportBackend) -> "CostModel":
        if transport == TransportBackend.DCN:
            return cls(alpha_us=cfg.sched_dcn_alpha_us,
                       beta_gbps=cfg.sched_dcn_beta_gbps)
        return cls(alpha_us=cfg.sched_alpha_us,
                   beta_gbps=cfg.sched_beta_gbps)

    @classmethod
    def tiered(cls, cfg: ACCLConfig) -> "CostModel":
        """Both tiers at once: default = the ICI pair (intra-slice
        steps), plus the DCN pair for steps marked ``transport=DCN``."""
        return cls(alpha_us=cfg.sched_alpha_us,
                   beta_gbps=cfg.sched_beta_gbps,
                   dcn_alpha_us=cfg.sched_dcn_alpha_us,
                   dcn_beta_gbps=cfg.sched_dcn_beta_gbps)

    def for_transport(self, transport) -> "CostModel":
        """The single-tier parameters pricing ``transport`` under this
        model (identity unless this is a tiered model and the step
        crosses slices)."""
        if (transport == TransportBackend.DCN
                and self.dcn_alpha_us is not None):
            return CostModel(alpha_us=self.dcn_alpha_us,
                             beta_gbps=self.dcn_beta_gbps)
        return self

    def step_us(self, hops: int, link_bytes: float, channels: int,
                transport: Optional[TransportBackend] = None) -> float:
        m = self.for_transport(transport)
        bw = link_bytes / (max(channels, 1) * m.beta_gbps * 1e3)
        return m.alpha_us * hops + bw


def model_for(cfg: ACCLConfig, topo: Topology) -> CostModel:
    """THE cost model for one topology: tiered (per-step ICI/DCN
    pricing) when the topology carries a DCN axis, the single
    transport's parameters otherwise — byte-identical to the
    pre-two-tier pricing everywhere a mesh has only one tier."""
    if topo.dcn_axis is not None:
        return CostModel.tiered(cfg)
    return CostModel.from_config(cfg, topo.transport)


def _ceil_log2(n: int) -> int:
    return max(1, math.ceil(math.log2(n))) if n > 1 else 0


def link_cost_us(cfg: ACCLConfig, transport, nbytes: int,
                 hops: int = 1, channels: int = 1) -> float:
    """Price ``hops`` sequential ring hops of ``nbytes`` each on one
    link with the session's α-β parameters — the cost-model primitive
    consumers OUTSIDE the plan search use to arbitrate cross-axis link
    occupancy (the pipeline-schedule arbiter prices its per-tick
    activation relay against the stage's tp collective through this;
    see ``models/pipeline.resolve_pp_schedule`` and
    docs/scheduling.md).  ``channels=2`` models a bidirectional hop
    (both directions of the link carrying half the payload each).
    ``transport`` accepts the enum or its string value; an unknown
    string raises (a silent ICI default would misprice DCN links)."""
    if not isinstance(transport, TransportBackend):
        transport = TransportBackend(transport)
    model = CostModel.from_config(cfg, transport)
    # hops pay α each; the payload crosses each hop's link once
    return model.alpha_us * hops + hops * float(nbytes) / (
        max(channels, 1) * model.beta_gbps * 1e3)


# ---------------------------------------------------------------------------
# schedule plans
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScheduleStep:
    """One phase of a synthesized schedule.

    ``axis`` indexes ``Topology.axes`` (None = the whole communicator as
    one logical group — flat star / tree / single ring). ``hops`` is the
    per-rank sequential hop count the cost model charges; ``link_bytes``
    the traffic through the busiest link; ``channels`` the concurrently
    driven link directions. ``deps`` are indices of steps that must
    complete first. ``chunk`` is the pipeline-chunk index for chunked
    multi-axis schedules (None = the step operates on the whole
    payload): the validator runs the ownership algebra once per chunk,
    so cross-chunk aliasing — a step folding another chunk's phase —
    is a hard error, not an accounting blur. ``transport`` is the tier
    THIS step's links ride (None = the topology's transport): on a
    two-tier topology cross-slice steps carry ``DCN`` and are priced
    with the DCN α/β pair while intra-slice steps carry ``ICI`` — the
    per-step pricing that keeps a mixed ICI/DCN plan honest (one
    transport pricing every step would misprice it by construction)."""

    index: int
    kind: str                    # reduce_scatter | all_gather | allreduce
    #                            # | reduce | bcast
    axis: Optional[int]
    group: int                   # participating group size
    hops: int
    link_bytes: float
    channels: int
    deps: Tuple[int, ...]
    chunk: Optional[int] = None
    transport: Optional[TransportBackend] = None


@dataclasses.dataclass(frozen=True)
class SchedulePlan:
    """A synthesized collective schedule: the step DAG, its predicted
    α-β cost, the Algorithm family that executes it, and where the
    decision came from (``cost_model`` — the search picked it;
    ``override`` — a non-default legacy register pinned the legacy
    choice; ``legacy`` — synthesis disabled / single-axis / DCN)."""

    op: operation
    shape: str
    algorithm: Algorithm
    topology: Topology
    steps: Tuple[ScheduleStep, ...]
    predicted_us: float
    source: str
    params: Tuple[Tuple[str, object], ...] = ()

    def param(self, key: str, default=None):
        for k, v in self.params:
            if k == key:
                return v
        return default

    def describe(self) -> str:
        legs = " -> ".join(
            f"{s.kind}[axis={'*' if s.axis is None else s.axis},"
            f"g={s.group},h={s.hops}]" for s in self.steps)
        return (f"{self.op.name}:{self.shape}({self.algorithm.value}) "
                f"{legs} ~{self.predicted_us:.1f}us [{self.source}]")


def _payload_total(op: operation, nbytes: int, world: int) -> int:
    """Normalize select()'s per-op byte convention to the logical FULL
    payload N the cost formulas are written in (allreduce: per-rank
    payload; allgather: per-block bytes -> gathered result;
    reduce_scatter: total input bytes)."""
    if op == operation.allgather:
        return nbytes * world
    return nbytes


def _step_transport(topo: Optional[Topology],
                    axis) -> Optional[TransportBackend]:
    """The tier one step's links ride: on a two-tier topology, steps on
    the DCN axis — and whole-communicator steps, whose rings must cross
    slices — are DCN, every other axis is intra-slice ICI. Topologies
    without a DCN axis mark nothing (single-transport pricing)."""
    if topo is None or topo.dcn_axis is None:
        return None
    if axis is None or axis == topo.dcn_axis:
        return TransportBackend.DCN
    return TransportBackend.ICI


def _mk_steps(specs, model: CostModel, topo: Optional[Topology] = None):
    steps = []
    for i, (kind, axis, group, hops, link_bytes, channels) in enumerate(specs):
        steps.append(ScheduleStep(
            index=i, kind=kind, axis=axis, group=group, hops=hops,
            link_bytes=float(link_bytes), channels=channels,
            deps=(i - 1,) if i else (),
            transport=_step_transport(topo, axis)))
    cost = sum(model.step_us(s.hops, s.link_bytes, s.channels, s.transport)
               for s in steps)
    return tuple(steps), cost


def _gen_xla(op, topo: Topology, N: int, model: CostModel):
    """XLA single-shot: the latency-optimal "rendezvous single move" —
    modeled at log-depth latency with ring-optimal bytes (XLA's own
    fused schedules). One launch regardless; the step split below is
    the cost/validation model, not the program structure."""
    P, k = topo.world, 2 if topo.bidirectional else 1
    lg, per = _ceil_log2(P), N * (P - 1) / P
    if op == operation.allreduce:
        specs = [("reduce_scatter", None, P, lg, per, k),
                 ("all_gather", None, P, lg, per, k)]
    elif op == operation.allgather:
        specs = [("all_gather", None, P, lg, per, k)]
    else:
        specs = [("reduce_scatter", None, P, lg, per, k)]
    steps, cost = _mk_steps(specs, model, topo)
    return SchedulePlan(op, "xla", Algorithm.XLA, topo, steps, cost, "")


def _gen_ring(op, topo: Topology, N: int, model: CostModel,
              channels: int, shape: str, algorithm: Algorithm):
    """Single logical ring (channels=1) or k counter-rotating rings
    (channels=2: every link direction busy, per-direction bytes
    halved). The flat-ring path the multi-axis schedule A/Bs against."""
    P = topo.world
    per = N * (P - 1) / P
    if op == operation.allreduce:
        specs = [("reduce_scatter", None, P, P - 1, per, channels),
                 ("all_gather", None, P, P - 1, per, channels)]
    elif op == operation.allgather:
        specs = [("all_gather", None, P, P - 1, per, channels)]
    else:
        specs = [("reduce_scatter", None, P, P - 1, per, channels)]
    steps, cost = _mk_steps(specs, model, topo)
    return SchedulePlan(op, shape, algorithm, topo, steps, cost, "")


def _gen_tree(op, topo: Topology, N: int, model: CostModel):
    """Binary tree (recursive doubling): log-depth, full payload per
    round — the latency family for rooted rendezvous, kept in the
    candidate space for completeness (allreduce only)."""
    if op != operation.allreduce:
        return None
    P, k = topo.world, 2 if topo.bidirectional else 1
    lg = _ceil_log2(P)
    specs = [("reduce", None, P, lg, N * lg, k),
             ("bcast", None, P, lg, N * lg, k)]
    steps, cost = _mk_steps(specs, model, topo)
    return SchedulePlan(op, "tree", Algorithm.TREE, topo, steps, cost, "")


def _gen_flat(op, topo: Topology, N: int, model: CostModel):
    """Flat star (root fan-in/out): 2 hops, root links carry (P-1)·N."""
    if op != operation.allreduce:
        return None
    P = topo.world
    specs = [("reduce", None, P, 1, N * (P - 1), 1),
             ("bcast", None, P, 1, N * (P - 1), 1)]
    steps, cost = _mk_steps(specs, model, topo)
    return SchedulePlan(op, "flat", Algorithm.FLAT, topo, steps, cost, "")


def _multiaxis_phase_specs(op, topo: Topology, N: int):
    """The per-axis phase list of the multi-axis decomposition, shared
    by the sequential (:func:`_gen_multiaxis`) and chunk-pipelined
    (:func:`_gen_pipeline`) candidates — one source of truth for what
    each leg moves and charges."""
    k = 2 if topo.bidirectional else 1
    rs_specs, ag_specs = [], []
    m = float(N)
    # scatter the LAST axis first (the builders' column axis — the heavy
    # leg shrinks the payload fastest), gather back in reverse
    for ax in reversed(range(len(topo.axes))):
        s = topo.axes[ax]
        rs_specs.append(("reduce_scatter", ax, s, s - 1,
                         m * (s - 1) / s, k))
        m /= s
    for ax, s in enumerate(topo.axes):
        ag_specs.append(("all_gather", ax, s, s - 1, m * (s - 1), k))
        m *= s
    if op == operation.allreduce:
        return rs_specs + ag_specs
    if op == operation.allgather:
        return ag_specs
    return rs_specs


def _gen_multiaxis(op, topo: Topology, N: int, model: CostModel):
    """Axis-by-axis torus decomposition (arxiv 2404.15888): reduce-
    scatter down every axis in order (payload shrinking by sᵢ each
    leg), then all-gather back up in reverse — allreduce composes both
    sweeps, allgather/reduce_scatter take one.  Per-axis leg i moves
    Mᵢ·(sᵢ−1)/sᵢ through that AXIS's links only — the busiest link
    carries N·(s₀−1)/s₀ < N·(P−1)/P of the flat ring, and the hop count
    is Σ(sᵢ−1) < P−1."""
    if not topo.multi_axis:
        return None
    specs = _multiaxis_phase_specs(op, topo, N)
    steps, cost = _mk_steps(specs, model, topo)
    return SchedulePlan(
        op, "multiaxis", Algorithm.MULTIAXIS, topo, steps, cost, "",
        params=(("shape2d", tuple(topo.axes)),))


def _gen_pipeline(op, topo: Topology, N: int, model: CostModel,
                  chunks: int, startup_us: float):
    """Chunk-pipelined multi-axis schedule (the wafer-scale-reduce
    overlap win, arxiv 2404.15888): the payload splits into ``chunks``
    pieces, each running the full per-axis phase chain, and chunk c's
    phase k+1 leg overlaps chunk c+1's phase k leg — the phases ride
    DIFFERENT axes' links, so the second axis works exactly when the
    sequential schedule would leave it idle.  The step DAG carries the
    per-chunk dependencies (intra-chunk phase order + the same-phase
    link-occupancy edge to the previous chunk); predicted cost is the
    steady-state pipeline makespan
    ``max(phase costs) + (chunks-1)·startup`` — every non-bottleneck
    phase hides under the bottleneck phase's wire time, and each extra
    chunk pays one pipeline-fill ``startup_us`` (calibrated on real ICI
    by ``bench.autotune_sched_synth``) — vs the sequential candidate's
    ``sum(phase costs)``.  A v5e 2x2 did not bear the overlap out (a
    4-chunk 1 GiB allreduce took 47.1 ms against 44.0 ms sequential),
    so :func:`resolve` leaves this candidate out of the ICI window."""
    if not topo.multi_axis or chunks < 2:
        return None
    specs = _multiaxis_phase_specs(op, topo, N)
    n_ph = len(specs)
    steps: List[ScheduleStep] = []
    for c in range(chunks):
        for k, (kind, axis, group, hops, link_bytes, channels) \
                in enumerate(specs):
            deps = []
            if k:
                deps.append(c * n_ph + k - 1)      # my previous phase
            if c:
                deps.append((c - 1) * n_ph + k)    # phase k's links free
            steps.append(ScheduleStep(
                index=c * n_ph + k, kind=kind, axis=axis, group=group,
                hops=hops, link_bytes=float(link_bytes) / chunks,
                channels=channels, deps=tuple(deps), chunk=c,
                transport=_step_transport(topo, axis)))
    phase_costs = [model.step_us(hops, link_bytes, channels,
                                 _step_transport(topo, axis))
                   for (_, axis, _, hops, link_bytes, channels) in specs]
    cost = max(phase_costs) + (chunks - 1) * float(startup_us)
    return SchedulePlan(
        op, "pipeline", Algorithm.MULTIAXIS, topo, tuple(steps), cost, "",
        params=(("shape2d", tuple(topo.axes)),
                ("pipeline_chunks", int(chunks))))


def in_latency_tier(nbytes: int, cfg: ACCLConfig) -> bool:
    """Whether a payload of ``nbytes`` resolves through the latency
    tier — THE membership test, shared by :func:`resolve`'s plan keying
    and the serving tier's control-message sizing (a disaggregation
    handoff header must ride the eager fast path, and asserting it
    through this helper keeps the two layers from drifting on what
    "sub-threshold" means)."""
    return nbytes < cfg.latency_tier_threshold


def _latency_plan(op: operation, topo: Topology, nbytes: int,
                  cfg: ACCLConfig) -> SchedulePlan:
    """The α-dominated small-message regime ("Optimizing Communication
    for Latency Sensitive HPC Applications", arxiv 2403.18374: the
    algorithm choice FLIPS at small sizes): below
    ``cfg.latency_tier_threshold`` the bandwidth terms are noise and
    hop count rules, so the candidate space is the latency family —
    XLA's log-depth single shot, the 2-hop flat star (root links carry
    (P−1)·N, irrelevant at token-sized payloads) and the binary tree —
    and the argmin of predicted α-β cost wins.  Flat/tree only exist
    for allreduce (the rooted builders); allgather/reduce_scatter keep
    the log-depth single shot, still resolved (and counted) through
    the tier so the decision is attributable."""
    model = model_for(cfg, topo)
    N = _payload_total(op, nbytes, topo.world)
    cands = [p for p in (_gen_xla(op, topo, N, model),
                         _gen_flat(op, topo, N, model),
                         _gen_tree(op, topo, N, model)) if p is not None]
    return min(cands, key=lambda p: p.predicted_us)


def _gen_hier(op, topo: Topology, N: int, model: CostModel):
    """The existing two-tier split (row reduce-scatter, cross-axis
    allreduce on the shard, row all-gather) — kept as its own candidate
    so the search covers the historical family."""
    if op != operation.allreduce or len(topo.axes) != 2:
        return None
    rows, cols = topo.axes
    k = 2 if topo.bidirectional else 1
    m = N / cols
    lg = _ceil_log2(rows)
    specs = [("reduce_scatter", 1, cols, cols - 1, N * (cols - 1) / cols, k),
             ("allreduce", 0, rows, 2 * lg, 2 * m * (rows - 1) / rows, k),
             ("all_gather", 1, cols, cols - 1, N * (cols - 1) / cols, k)]
    steps, cost = _mk_steps(specs, model, topo)
    return SchedulePlan(op, "hier", Algorithm.HIERARCHICAL, topo, steps,
                        cost, "")


def _gen_twotier(op, topo: Topology, N: int, model: CostModel,
                 wire: str, wire_ratio: float = 1.0):
    """The DCN two-tier schedule (``hierarchical.build_twotier_*``):
    intra-slice reduce-scatter (full precision, per-slice ICI rings) →
    ONE cross-slice exchange over DCN with the shard staged in the
    ``dcn_wire_dtype`` codec (gather + full-precision decompress-fold
    for the reducing ops — each contribution rounds exactly once; a
    direct exchange, so α is paid once while the slice NIC serializes
    the (S−1) shard payloads) → full-precision intra-slice all-gather.
    ``wire_ratio`` scales the DCN leg to effective wire bytes (1.0 =
    full precision, the ``"off"``/two-tier-full candidate); the ICI
    legs never compress.  Requires a topology whose axis 0 is the DCN
    axis (``topology_of`` on a host-aligned multi-slice mesh)."""
    if topo.dcn_axis != 0 or len(topo.axes) != 2:
        return None
    S, L = topo.axes
    k = 2 if topo.bidirectional else 1
    m = N / L                      # the per-slice shard on the DCN leg
    block = N / (S * L)            # one rank's allgather block
    if op == operation.allreduce:
        specs = [("reduce_scatter", 1, L, L - 1, N * (L - 1) / L, k),
                 ("allreduce", 0, S, 1, m * (S - 1) * wire_ratio, 1),
                 ("all_gather", 1, L, L - 1, N * (L - 1) / L, k)]
    elif op == operation.allgather:
        specs = [("all_gather", 0, S, 1, block * (S - 1) * wire_ratio, 1),
                 ("all_gather", 1, L, L - 1, N * (L - 1) / L, k)]
    else:
        specs = [("reduce_scatter", 1, L, L - 1, N * (L - 1) / L, k),
                 ("reduce_scatter", 0, S, 1,
                  m * (S - 1) / S * wire_ratio, 1)]
    steps, cost = _mk_steps(specs, model, topo)
    return SchedulePlan(
        op, "twotier", Algorithm.TWOTIER, topo, steps, cost, "",
        params=(("shape2d", tuple(topo.axes)),
                ("dcn_wire_dtype", wire)))


def _twotier_candidates(op, topo: Topology, nbytes: int, N: int,
                        model: CostModel, cfg: ACCLConfig,
                        count: Optional[int] = None) -> List[SchedulePlan]:
    """The two-tier pair for a DCN multi-slice topology: the COMPRESSED
    schedule (DCN leg at effective ``dcn_wire_dtype`` wire bytes — the
    ``cmatmul_wire_bytes`` pricing; ``count`` resolves the operand
    width from the call's ``nbytes`` convention) and the full-precision
    twin (wire ratio 1.0, the bit-exact ``"off"`` contract), so
    ``resolve()`` can honestly arbitrate two-tier-compressed vs
    two-tier-full vs flat vs legacy. Empty off two-tier topologies and
    at ``dcn_wire_dtype`` off for the compressed arm."""
    out = [_gen_twotier(op, topo, N, model, "off", 1.0)]
    wire = getattr(cfg, "dcn_wire_dtype", "off") or "off"
    if wire != "off" and nbytes > 0:
        ratio = dcn_wire_bytes(nbytes, wire, count) / nbytes
        if ratio < 1.0:
            out.append(_gen_twotier(op, topo, N, model, wire, ratio))
    return [p for p in out if p is not None]


def candidates(op: operation, topo: Topology, nbytes: int,
               cfg: ACCLConfig,
               count: Optional[int] = None) -> List[SchedulePlan]:
    """The full candidate space for one (op, topology, payload):
    every applicable generator's plan, cost-annotated."""
    model = model_for(cfg, topo)
    N = _payload_total(op, nbytes, topo.world)
    out = [_gen_xla(op, topo, N, model),
           _gen_multiaxis(op, topo, N, model),
           _gen_pipeline(op, topo, N, model, cfg.sched_pipeline_chunks,
                         cfg.sched_pipeline_startup_us),
           _gen_hier(op, topo, N, model),
           _gen_ring(op, topo, N, model, 1, "ring", Algorithm.RING),
           (_gen_ring(op, topo, N, model, 2, "kring", Algorithm.RING)
            if topo.world >= 4 else None),
           _gen_tree(op, topo, N, model),
           _gen_flat(op, topo, N, model)]
    out = [p for p in out if p is not None]
    out.extend(_twotier_candidates(op, topo, nbytes, N, model, cfg,
                                   count=count))
    return out


def _plan_for_algo(algo: Algorithm, op: operation, topo: Topology,
                   nbytes: int, cfg: ACCLConfig) -> SchedulePlan:
    """The plan describing what a LEGACY Algorithm choice executes —
    used when an override or disabled synthesis pins the old decision,
    so the observability tier still names the shape that ran."""
    model = model_for(cfg, topo)
    N = _payload_total(op, nbytes, topo.world)
    kring = topo.bidirectional and topo.world >= 4
    if algo in (Algorithm.RING, Algorithm.PALLAS):
        p = _gen_ring(op, topo, N, model, 2 if kring else 1,
                      "kring" if kring else "ring", algo)
    elif algo == Algorithm.HIERARCHICAL:
        t2 = topo if len(topo.axes) == 2 else None
        if t2 is None:
            from .hierarchical import factor2d
            shape = factor2d(topo.world)
            t2 = dataclasses.replace(topo, axes=tuple(shape)) if shape \
                else None
        p = _gen_hier(op, t2, N, model) if t2 is not None else None
        if p is None:
            p = _gen_xla(op, topo, N, model)
            p = dataclasses.replace(p, algorithm=algo)
    elif algo == Algorithm.TREE:
        p = _gen_tree(op, topo, N, model) or _gen_xla(op, topo, N, model)
    elif algo == Algorithm.FLAT:
        p = _gen_flat(op, topo, N, model) or _gen_xla(op, topo, N, model)
    elif algo == Algorithm.MULTIAXIS:
        p = _gen_multiaxis(op, topo, N, model)
        if p is None:
            raise ValueError(
                "MULTIAXIS needs a multi-axis topology (declare "
                "cfg.sched_mesh_shape or run on a coordinate grid)")
    else:
        p = dataclasses.replace(_gen_xla(op, topo, N, model), algorithm=algo)
    return p


def _full_authority_plan(op: operation, topo: Topology, nbytes: int,
                         cfg: ACCLConfig) -> SchedulePlan:
    """The ``sched_full_authority`` resolution: the argmin of predicted
    α-β cost over the WHOLE candidate family for this (op, topology,
    payload) — no threshold ladder, no seed pins, no separate latency
    tier (the small-payload flip to flat/tree falls out of the same
    search).  One execution-mapping rule: the ring-family shapes run
    the Pallas RDMA-over-ICI kernels on real chip links (the perf core
    the legacy ladder routed large ICI payloads to) and the plain
    ppermute ring elsewhere — the cost model prices the schedule shape,
    the transport picks its implementation."""
    cands = [p for p in candidates(op, topo, nbytes, cfg)
             if p.shape != "xla"]
    # With the ladder retired, the model must carry the fact the ladder
    # measured: XLA's fused single shot is latency-optimal but does NOT
    # counter-rotate segment parities, so its bandwidth term runs one
    # link direction — exactly the 2x the explicit chunked rings buy
    # (the reason ring_threshold existed). Priced here only; the
    # legacy-compatible costing elsewhere keeps the ladder-deferring
    # paths byte-identical.
    model = CostModel.from_config(cfg, topo.transport)
    N = _payload_total(op, nbytes, topo.world)
    cands.append(_gen_xla(
        op, dataclasses.replace(topo, bidirectional=False), N, model))
    best = min(cands, key=lambda p: p.predicted_us)
    if best.shape == "xla":
        # restore the live topology on the winning plan (the
        # single-direction costing is a pricing device, not a claim
        # about the mesh)
        best = dataclasses.replace(best, topology=topo)
    if (best.shape in ("ring", "kring")
            and topo.transport == TransportBackend.ICI):
        best = dataclasses.replace(best, algorithm=Algorithm.PALLAS)
    return best


# ---------------------------------------------------------------------------
# plan resolution (cached; the select() hook)
# ---------------------------------------------------------------------------

#: non-default values in these registers are autotune seeds / operator
#: hand tunes — they PIN the legacy decision for the op they govern
#: (the override/migration contract; see docs/scheduling.md)
_SEED_FIELDS: Dict[operation, Tuple[str, ...]] = {
    operation.allreduce: ("ring_threshold", "hier_threshold",
                          "dcn_hier_threshold", "pallas_threshold"),
    operation.allgather: ("ag_ring_threshold", "ag_pallas_threshold"),
    operation.reduce_scatter: ("rs_ring_threshold", "rs_pallas_threshold"),
}

_CFG_DEFAULTS = None


def _seed_overridden(op: operation, cfg: ACCLConfig) -> bool:
    global _CFG_DEFAULTS
    if _CFG_DEFAULTS is None:
        _CFG_DEFAULTS = ACCLConfig()
    return any(getattr(cfg, f) != getattr(_CFG_DEFAULTS, f)
               for f in _SEED_FIELDS.get(op, ()))


#: memoized plan store — an insertion-ordered dict used as an LRU bound
#: by :data:`_PLAN_CACHE_MAX` (a long-lived serving session resolving
#: many (op, topology, bucket, seeds) keys must not grow it without
#: limit — the ProgramCache discipline); hit/miss/evict tallies live
#: beside the metrics counters so ``ACCL.stats()`` can report them
#: without a metrics scan
_plan_cache: Dict[tuple, SchedulePlan] = {}
_plan_lock = threading.Lock()
_PLAN_CACHE_MAX = 4096
_plan_hits = 0
_plan_misses = 0
_plan_evictions = 0

#: session epoch baked into every plan-cache key: bumped by
#: ``ACCL.recover()`` so a plan synthesized before a rank death is
#: unreachable afterwards even when the (op, topology, bucket) key
#: collides — stale pre-death plans must never be dispatchable on the
#: shrunk mesh (docs/resilience.md §5)
_session_epoch = 0


def set_session_epoch(epoch: int) -> None:
    """Epoch hook (``ACCL.initialize()`` / ``ACCL.recover()``): key every
    subsequently synthesized plan by the session epoch."""
    global _session_epoch
    _session_epoch = int(epoch)


#: recalibration generation, also baked into every plan-cache key:
#: bumped by ``ACCL.recalibrate()`` when an online α/β refit is APPLIED
#: (obs/recal.py), so every plan priced at the stale registers becomes
#: unreachable and re-resolves at the new prices. Deliberately separate
#: from the session epoch — a recal must not collide with recover()'s
#: epoch machinery, and survives reset_plan_cache().
_recal_gen = 0


def bump_recal_generation() -> int:
    """Invalidate every cached plan priced at pre-refit α/β; returns the
    new generation."""
    global _recal_gen
    _recal_gen += 1
    return _recal_gen


def recal_generation() -> int:
    return _recal_gen


def reset_plan_cache() -> None:
    """Session hook (``ACCL.initialize()``): drop every cached plan —
    and the per-config fingerprint memo — so a fresh session
    re-synthesizes under its own config."""
    global _plan_hits, _plan_misses, _plan_evictions
    with _plan_lock:
        _plan_cache.clear()
        _plan_hits = _plan_misses = _plan_evictions = 0
        _fp_cache.clear()
        _dcn_wire_totals["pre_bytes"] = 0.0
        _dcn_wire_totals["post_bytes"] = 0.0


def plan_cache_stats() -> Dict[str, int]:
    """Synth plan-cache introspection for ``ACCL.stats()`` — the
    program-cache shape: live size, LRU bound, and the session's
    hit/miss/evict tallies (the same events the
    ``accl_sched_plan_cache_total`` counter exports)."""
    with _plan_lock:
        return {"plans": len(_plan_cache), "max_size": _PLAN_CACHE_MAX,
                "hits": _plan_hits, "misses": _plan_misses,
                "evictions": _plan_evictions,
                "recal_generation": _recal_gen}


#: running per-session totals of the two-tier cross-slice leg's bytes
#: (pre- and post-compression), kept beside the
#: ``accl_dcn_wire_bytes_total`` counters so ``ACCL.stats()`` reports
#: them without a metrics scan (the plan-cache-stats shape)
_dcn_wire_totals = {"pre_bytes": 0.0, "post_bytes": 0.0}


def note_dcn_wire_bytes(op: operation, plan: SchedulePlan, nbytes: int,
                        count: Optional[int] = None) -> None:
    """Account one dispatch of a two-tier plan's CROSS-SLICE leg:
    per-rank DCN bytes before compression (the full-precision payload
    the leg would move at ``dcn_wire_dtype="off"``) and after (the
    effective wire bytes the compressed schedule actually moves) —
    ``accl_dcn_wire_bytes_total{op,dtype,stage}``. Called by
    ``algorithms.select_plan`` once per dispatch resolution, so the
    pre/post ratio over a workload is readable straight off the
    counters (and summed into ``dcn_wire_totals`` for stats())."""
    if plan.shape != "twotier":
        return
    shape = plan.param("shape2d")
    wire = plan.param("dcn_wire_dtype", "off") or "off"
    if not shape or len(shape) != 2:
        return
    S, L = shape
    N = _payload_total(op, nbytes, S * L)
    if op == operation.allgather:
        pre = (N / (S * L)) * (S - 1)
    elif op == operation.reduce_scatter:
        pre = (N / L) * (S - 1) / S
    else:
        pre = (N / L) * (S - 1)
    ratio = (dcn_wire_bytes(nbytes, wire, count) / nbytes
             if nbytes > 0 else 1.0)
    post = pre * ratio
    _metrics.inc("accl_dcn_wire_bytes_total", value=pre,
                 labels=(("op", op.name), ("dtype", wire),
                         ("stage", "pre")))
    _metrics.inc("accl_dcn_wire_bytes_total", value=post,
                 labels=(("op", op.name), ("dtype", wire),
                         ("stage", "post")))
    with _plan_lock:
        _dcn_wire_totals["pre_bytes"] += pre
        _dcn_wire_totals["post_bytes"] += post


def dcn_wire_totals() -> Dict[str, float]:
    """Session totals of the two-tier cross-slice leg's pre/post
    compression bytes — the ``ACCL.stats()`` surface."""
    with _plan_lock:
        return dict(_dcn_wire_totals)


#: per-config memo of :func:`_cost_fingerprint` — the tuple build walks
#: ten config fields and sits on the per-op dispatch path (every
#: ``resolve()`` call), so it is computed once per config OBJECT per
#: session. Keyed by id() with the config kept strongly referenced, so
#: a recycled id can never alias a dead config; bounded (cleared at
#: _FP_CACHE_MAX and by reset_plan_cache). Configs are value objects —
#: every mutation path in the repo goes through ``ACCLConfig.replace``
#: / the ``ACCL.config`` setter, which produce fresh objects; mutating
#: a cost field IN PLACE on a config that already resolved a plan is
#: unsupported (the seeds tuple in the resolve key is re-read each
#: call and stays exact either way).
_fp_cache: Dict[int, Tuple[ACCLConfig, tuple]] = {}
_FP_CACHE_MAX = 256


def _cost_fingerprint(cfg: ACCLConfig) -> tuple:
    entry = _fp_cache.get(id(cfg))
    if entry is not None and entry[0] is cfg:
        return entry[1]
    fp = (cfg.sched_synthesis, cfg.sched_alpha_us, cfg.sched_beta_gbps,
          cfg.sched_dcn_alpha_us, cfg.sched_dcn_beta_gbps,
          cfg.latency_tier_threshold, cfg.sched_pipeline_chunks,
          cfg.sched_pipeline_startup_us, cfg.sched_full_authority,
          cfg.dcn_wire_dtype)
    if len(_fp_cache) >= _FP_CACHE_MAX:
        _fp_cache.clear()
    _fp_cache[id(cfg)] = (cfg, fp)
    return fp


def resolve(op: operation, nbytes: int, comm, cfg: ACCLConfig,
            legacy: Algorithm, count: Optional[int] = None,
            wire_inert: bool = False,
            xla_gathers: bool = False) -> SchedulePlan:
    """Resolve THE schedule plan for one call — the cost-model search,
    memoized per (op, topology, size-bucket, legacy decision, cost
    params).  ``legacy`` is what the scalar-threshold ladder chose; the
    plan deviates from it only when

    * synthesis is enabled (``cfg.sched_synthesis``),
    * the transport is single-slice — UNLESS ``cfg.dcn_wire_dtype``
      opts a host-aligned multi-slice mesh into the DCN two-tier
      window, where the per-tier cost model arbitrates the compressed
      two-tier schedule against its full-precision twin, the flat ring
      and the legacy ladder (``dcn_wire_dtype="off"``, calls whose
      wire is inert (``wire_inert``: an arith wire already owns the
      hops, or a payload dtype the codec refuses to narrow) and
      non-host-aligned DCN meshes resolve the legacy ladder
      byte-identically, pinned; inside the window the opt-in register
      outranks generic seeds — a seeded ladder pins the BASELINE the
      two-tier candidates must strictly beat, not the window),
    * no governing legacy register carries an autotune seed
      (:data:`_SEED_FIELDS` — seeds are explicit overrides), and
    * EITHER the payload sits below ``cfg.latency_tier_threshold`` —
      the α-dominated small-message tier, where the latency family
      (flat / tree / xla log-depth) is searched on any topology
      (:func:`_latency_plan`, source ``latency_tier``) — OR the
      topology has ≥ 2 axes (declared or coordinate-detected; a
      DECLARED 3-axis shape dispatches a real 3-axis decomposition)
      and the multi-axis candidate — sequential or chunk-PIPELINED
      (``cfg.sched_pipeline_chunks``; the pipelined shape wins exactly
      where ``max(phase costs) + (chunks-1)·startup`` undercuts the
      sequential sum) — beats the legacy family's predicted α-β cost.
      On ICI that window is XLA's own collective against the legacy
      family instead (source ``cost_model`` whichever wins), save for
      calls marked ``xla_gathers``: an allreduce whose arith
      decompresses before arithmetic, which XLA's path runs as an
      all-gather of the whole buffer.

    ``cfg.sched_full_authority`` (default off) short-circuits the seed
    and tier rules: the argmin over the whole candidate family decides
    per size bucket on EVERY non-DCN topology, single-axis included
    (source ``full_authority`` — the "synthesis becomes the only
    scheduler" migration switch).

    Everything else returns the legacy decision wrapped in its plan —
    so meshes with default config resolve EXACTLY as before the
    refactor for every payload at or above the latency threshold
    (pinned by tests/test_synth.py equivalence tests)."""
    topo = topology_of(comm, cfg)
    # the governing legacy registers are part of the key: a seeded config
    # must never hit a default-config plan (and vice versa) even when
    # both ladders happened to pick the same legacy algorithm
    seeds = tuple(getattr(cfg, f) for f in _SEED_FIELDS.get(op, ()))
    # the latency threshold cuts INSIDE a size bucket (8 KiB sits in the
    # <=16KiB bin), so the tier membership must be part of the key — a
    # sub-threshold payload must never be served the legacy plan its
    # above-threshold bucket-mate cached (and vice versa)
    in_tier = in_latency_tier(nbytes, cfg)
    # DCN with the wire register SET only: the operand itemsize prices
    # the wire ratio (a f64 payload compresses 4:1 where f32 does 2:1)
    # and an inert wire closes the two-tier window — both cut inside a
    # size bucket, so both join the key there (f32 assumed when the
    # call's element count is unknown, the cmatmul_wire_bytes
    # convention). Everywhere else — non-DCN transports AND default
    # "off" DCN sessions — neither can affect the plan, and keying on
    # them would only split cache entries for nothing.
    if (topo.transport == TransportBackend.DCN
            and getattr(cfg, "dcn_wire_dtype", "off") not in (None, "off")):
        wire_key = ((nbytes // count) if count else 4, bool(wire_inert))
    else:
        wire_key = None
    key = (op, topo, _metrics.size_bucket(nbytes), in_tier,
           legacy, seeds, _cost_fingerprint(cfg), wire_key,
           bool(xla_gathers), _session_epoch, _recal_gen)
    global _plan_hits, _plan_misses, _plan_evictions
    with _plan_lock:
        plan = _plan_cache.get(key)
        if plan is not None:
            _plan_hits += 1
            # refresh recency (dicts iterate in insertion order, so the
            # eviction below pops the least-recently-USED key only if
            # hits re-insert — the ProgramCache move_to_end discipline)
            del _plan_cache[key]
            _plan_cache[key] = plan
    if plan is not None:
        _metrics.inc("accl_sched_plan_cache_total",
                     labels=(("event", "hit"),))
        return plan
    with _plan_lock:
        _plan_misses += 1
    _metrics.inc("accl_sched_plan_cache_total", labels=(("event", "miss"),))
    if not topo.multi_axis:
        # survivor-subset honesty: when this mesh HAD torus structure and
        # lost it (a holed grid, a stale declared shape on a shrunk
        # communicator), the single-axis fallback is the correct plan but
        # the lost multi-axis schedule must be attributable — counted
        # once per synthesized plan, the cmatmul-fallback discipline
        reason = degraded_reason(comm, cfg)
        if reason is not None:
            _metrics.inc("accl_select_decline_total",
                         labels=(("op", op.name), ("reason", reason)))

    if not cfg.sched_synthesis or op not in SYNTH_OPS:
        plan = dataclasses.replace(
            _plan_for_algo(legacy, op, topo, nbytes, cfg), source="legacy")
    elif topo.transport == TransportBackend.DCN:
        # the DCN two-tier window — OPT-IN via ``cfg.dcn_wire_dtype``:
        # with the register off (the default) every DCN resolution is
        # the legacy ladder's decision, byte-identical to pre-refactor
        # (pinned by tests/test_synth.py) — which also covers calls
        # whose wire is INERT: an ARITH wire already compressing every
        # hop, or a payload dtype the codec refuses to narrow (ints,
        # bf16/f16) — the builders stand the per-leg codec down for
        # both, and pricing or accounting a codec that will not run
        # would be dishonest. With a wire
        # dtype set on a host-aligned multi-slice topology, the
        # per-tier cost model arbitrates two-tier-compressed vs
        # two-tier-full vs the flat ring vs the legacy plan (strict
        # improvement; ties keep the baseline). The wire register is
        # ITSELF a non-default opt-in and outranks generic autotune
        # seeds here — seeds pin the legacy BASELINE the two-tier
        # candidates must strictly beat, not the window (otherwise
        # ``autotune_session``'s own threshold stages would make its
        # ``dcn_twotier`` go/no-go unreachable in the very config it
        # produces; a tuned deployment that never sets the register
        # stays exactly pre-refactor). A wire request on a mesh with
        # no slice boundary declines visibly (counted once per
        # synthesized plan, the degraded-decline discipline).
        wire = "off" if wire_inert \
            else (getattr(cfg, "dcn_wire_dtype", "off") or "off")
        if wire != "off" and topo.dcn_axis is None:
            _metrics.inc("accl_select_decline_total",
                         labels=(("op", op.name),
                                 ("reason", "dcn_no_host_shape")))
        if wire == "off" or topo.dcn_axis is None:
            plan = dataclasses.replace(
                _plan_for_algo(legacy, op, topo, nbytes, cfg),
                source="legacy")
        else:
            model = model_for(cfg, topo)
            N = _payload_total(op, nbytes, topo.world)
            best = _plan_for_algo(legacy, op, topo, nbytes, cfg)
            kring = topo.bidirectional and topo.world >= 4
            flat_ring = _gen_ring(
                op, topo, N, model, 2 if kring else 1,
                "kring" if kring else "ring", Algorithm.RING)
            for cand in ([flat_ring]
                         + _twotier_candidates(op, topo, nbytes, N,
                                               model, cfg, count=count)):
                if cand is not None \
                        and cand.predicted_us < best.predicted_us:
                    best = cand
            plan = dataclasses.replace(best, source="cost_model")
    elif cfg.sched_full_authority:
        # full authority (the migration switch): the per-size-bucket
        # argmin over the WHOLE candidate family retires the scalar
        # ladder on every topology — single-axis included — and seeds
        # no longer pin (the ladder they seed is retired with them).
        # The DCN guard above still outranks the flag.
        plan = dataclasses.replace(
            _full_authority_plan(op, topo, nbytes, cfg),
            source="full_authority")
    elif in_tier and not _seed_overridden(op, cfg):
        # the small-message latency tier: α dominates, so the cost model
        # searches the latency family (flat/tree/xla) on ANY topology —
        # single-axis meshes included (the one place synthesis deviates
        # without a torus). Seeded registers still pin the ladder, and
        # the DCN guard above keeps the two-tier story intact.
        plan = dataclasses.replace(
            _latency_plan(op, topo, nbytes, cfg), source="latency_tier")
    elif not topo.multi_axis:
        plan = dataclasses.replace(
            _plan_for_algo(legacy, op, topo, nbytes, cfg), source="legacy")
    elif _seed_overridden(op, cfg):
        plan = dataclasses.replace(
            _plan_for_algo(legacy, op, topo, nbytes, cfg), source="override")
    else:
        # the multi-axis window — strict improvement over the baseline
        # required (ties keep the simpler schedule). On ICI the baseline
        # is XLA's own collective at the live topology's pricing, and
        # only the legacy family challenges it: on a v5e 2x2 XLA ran
        # every op and size fastest, the sequential multi-axis shape
        # moves the same bytes in no fewer hops (at best a tie on
        # paper), and the pipelined shape's overlap did not show on the
        # chip (docs/scheduling.md). Elsewhere — and for an allreduce
        # XLA would run as a whole-buffer gather (``xla_gathers``, which
        # _gen_xla does not price) — the sequential decomposition and
        # the chunk-pipelined variant compete against the legacy family,
        # checked in (multiaxis, pipeline) order, so the pipelined
        # candidate wins exactly where
        # max(phase costs) + (chunks-1)·startup < sum(phase costs)
        legacy_plan = _plan_for_algo(legacy, op, topo, nbytes, cfg)
        model = CostModel.from_config(cfg, topo.transport)
        N = _payload_total(op, nbytes, topo.world)
        if topo.transport == TransportBackend.ICI and not xla_gathers:
            best, rivals = _gen_xla(op, topo, N, model), (legacy_plan,)
        else:
            best = legacy_plan
            rivals = (_gen_multiaxis(op, topo, N, model),
                      _gen_pipeline(op, topo, N, model,
                                    cfg.sched_pipeline_chunks,
                                    cfg.sched_pipeline_startup_us))
        for cand in rivals:
            if cand is not None and cand.predicted_us < best.predicted_us:
                best = cand
        plan = dataclasses.replace(best, source="cost_model")
    _metrics.inc("accl_sched_plan_total",
                 labels=(("op", op.name), ("shape", plan.shape),
                         ("source", plan.source)))
    with _plan_lock:
        if key not in _plan_cache and len(_plan_cache) >= _PLAN_CACHE_MAX:
            evicted = next(iter(_plan_cache))
            del _plan_cache[evicted]
            _plan_evictions += 1
            _metrics.inc("accl_sched_plan_cache_total",
                         labels=(("event", "evict"),))
        _plan_cache[key] = plan
    return plan


def resolve_publish_route(comm, cfg: ACCLConfig, nbytes: int,
                          count: Optional[int] = None
                          ) -> Optional[SchedulePlan]:
    """Price the weight-publication re-shard route
    (``models/publish.py``): the fused program's per-bucket dp
    all-gather leg, resolved through the SAME ladder + cost-model
    arbitration as any other collective (``_select_legacy`` →
    :func:`resolve`) so the ticket's ``plan_source``/``plan_shape``
    honesty pair means exactly what it means on the dispatch path —
    including the DCN two-tier window, where the cross-slice hop of a
    multi-slice publication is priced at the effective
    :func:`dcn_wire_bytes`.  ``nbytes`` is the per-block gather payload
    (the allgather byte convention).  Returns None when no communicator
    is live (single-process bring-up prices nothing)."""
    if comm is None or cfg is None:
        return None
    from . import algorithms
    legacy = algorithms._select_legacy(operation.allgather, nbytes, comm,
                                       cfg, count=count)
    return resolve(operation.allgather, nbytes, comm, cfg, legacy,
                   count=count)


# ---------------------------------------------------------------------------
# schedule validation: the ownership algebra
# ---------------------------------------------------------------------------

def _rank_coords(rank: int, axes: Sequence[int]) -> Tuple[int, ...]:
    out = []
    for s in reversed(axes):
        out.append(rank % s)
        rank //= s
    return tuple(reversed(out))


def _axis_groups(axes: Sequence[int], axis: Optional[int],
                 world: int) -> List[List[int]]:
    if axis is None:
        return [list(range(world))]
    groups: Dict[tuple, List[int]] = {}
    for r in range(world):
        c = list(_rank_coords(r, axes))
        c[axis] = -1
        groups.setdefault(tuple(c), []).append(r)
    return list(groups.values())


def _expected_hops(shape: str, kind: str, group: int,
                   transport=None) -> int:
    """What the cost model must have charged for one step of this shape
    — the validator's independent recomputation."""
    if shape == "twotier":
        # intra-slice legs walk per-slice rings; the cross-slice leg is
        # ONE direct DCN exchange (α paid once, every shard straight to
        # its destination while the slice NIC serializes the payloads)
        return 1 if transport == TransportBackend.DCN else group - 1
    if shape in ("ring", "kring", "multiaxis", "pipeline"):
        # a pipeline chunk's leg walks the same per-axis ring as the
        # sequential schedule — chunking splits bytes, never hops
        return group - 1
    if shape == "flat":
        return 1
    if shape == "hier":
        return (2 * _ceil_log2(group) if kind == "allreduce"
                else group - 1)
    # xla / tree: log-depth
    return _ceil_log2(group)


def validate_plan(plan: SchedulePlan) -> None:
    """Prove a synthesized schedule correct by construction:

    1. the step dependency graph is acyclic (a topological order
       exists and every dep precedes its step);
    2. running the ownership algebra over the steps covers each
       (chunk, rank) requirement EXACTLY once — no chunk is delivered
       twice, no contribution is folded twice, and the final state
       matches the op's contract. For chunk-PIPELINED plans the algebra
       runs once per pipeline chunk over exactly that chunk's steps —
       each (chunk, axis-phase) must appear exactly once and in phase
       order, so a step folding another chunk's payload (cross-chunk
       aliasing), a repeated phase (double fold), or a chunk delivered
       out of phase order all fail its own chunk's algebra;
    3. every step's hop count matches the cost model's charge for its
       shape (α drift is a bug, not a tuning artifact).

    Raises ``ValueError`` with a specific message on any violation."""
    # -- 1. dependency DAG ------------------------------------------------
    order: List[int] = []
    done: set = set()
    pending = {s.index: set(s.deps) for s in plan.steps}
    while pending:
        ready = [i for i, d in pending.items() if d <= done]
        if not ready:
            raise ValueError(f"cyclic step dependencies: {pending}")
        for i in sorted(ready):
            order.append(i)
            done.add(i)
            del pending[i]
    steps = {s.index: s for s in plan.steps}

    # -- 3. hop counts ----------------------------------------------------
    for s in plan.steps:
        want = _expected_hops(plan.shape, s.kind, s.group, s.transport)
        if s.hops != want:
            raise ValueError(
                f"step {s.index} ({plan.shape}/{s.kind}, group {s.group}): "
                f"hops {s.hops} != cost-model {want}")

    # -- 2. ownership algebra --------------------------------------------
    chunk_ids = sorted({s.chunk for s in plan.steps}, key=lambda c: (c is
                                                                     None, c))
    if chunk_ids == [None]:
        _validate_ownership(plan, order, steps)
        return
    if None in chunk_ids:
        raise ValueError(
            "mixed chunked and unchunked steps in one plan: a pipeline "
            "phase outside every chunk's algebra is unaccountable")
    declared = plan.param("pipeline_chunks")
    if declared is not None and chunk_ids != list(range(int(declared))):
        raise ValueError(
            f"pipeline chunks {chunk_ids} != declared range of "
            f"{declared}: a missing or duplicated chunk lane")
    for c in chunk_ids:
        sub_order = [i for i in order if steps[i].chunk == c]
        try:
            _validate_ownership(plan, sub_order, steps)
        except ValueError as e:
            raise ValueError(f"pipeline chunk {c}: {e}") from None


def _validate_ownership(plan: SchedulePlan, order: Sequence[int],
                        steps: Dict[int, ScheduleStep]) -> None:
    """The ownership-algebra half of :func:`validate_plan`, run over one
    payload lane (the whole plan, or a single pipeline chunk's steps in
    the DAG's topological order)."""
    topo, P = plan.topology, plan.topology.world
    axes = topo.axes
    # state[r] maps chunk -> (frozenset of folded source ranks, times the
    # fully-formed chunk was DELIVERED to r). Chunks are the P-way
    # decomposition; owner(chunk c) == rank c (the flat convention the
    # multi-axis builders realign to).
    gatherish = plan.op == operation.allgather
    state: List[Dict[int, Tuple[frozenset, int]]] = []
    for r in range(P):
        if gatherish:
            state.append({r: (frozenset([r]), 1)})
        else:
            state.append({c: (frozenset([r]), 1) for c in range(P)})

    def fold(group: List[int], keep: Callable[[int, int], bool]) -> None:
        """Reduce-flavored exchange over `group`: every live chunk's
        contributions union across the group; member g keeps chunk c
        iff keep(g, c). A source contributing twice is a double fold."""
        live = sorted({c for g in group for c in state[g]})
        merged = {}
        for c in live:
            srcs: List[frozenset] = [state[g][c][0]
                                     for g in group if c in state[g]]
            union = frozenset().union(*srcs)
            if sum(len(s) for s in srcs) != len(union):
                raise ValueError(
                    f"chunk {c}: a source contribution folded twice "
                    f"in group {group}")
            merged[c] = union
        for g in group:
            state[g] = {c: (merged[c], 1) for c in live if keep(g, c)}

    def gather(group: List[int]) -> None:
        """All-gather over `group`: every member's chunks delivered to
        every other member; receiving a chunk twice (or already holding
        it) is double coverage."""
        owners: Dict[int, List[int]] = {}
        for g in group:
            for c in state[g]:
                owners.setdefault(c, []).append(g)
        for c, who in owners.items():
            if len(who) > 1:
                raise ValueError(
                    f"chunk {c} owned by {who} before all_gather: "
                    f"would be delivered {len(who)} times")
        for c, who in owners.items():
            src = who[0]
            val = state[src][c]
            for g in group:
                if g == src:
                    continue
                if c in state[g]:
                    raise ValueError(
                        f"chunk {c} re-delivered to rank {g}")
                state[g][c] = (val[0], 1)

    processed_axes: List[int] = []
    for i in order:
        s = steps[i]
        groups = _axis_groups(axes, s.axis, P)
        if s.kind == "reduce_scatter":
            if s.axis is not None:
                processed_axes.append(s.axis)
            scattered = list(processed_axes)

            def keep(g, c, scattered=scattered, axis=s.axis):
                if axis is None:
                    return c == g
                gc, cc = _rank_coords(g, axes), _rank_coords(c, axes)
                return all(gc[a] == cc[a] for a in scattered)

            for grp in groups:
                fold(grp, keep)
        elif s.kind == "all_gather":
            for grp in groups:
                gather(grp)
        elif s.kind == "allreduce":
            for grp in groups:
                fold(grp, lambda g, c: True)
        elif s.kind == "reduce":
            for grp in groups:
                root = grp[0]
                fold(grp, lambda g, c, root=root: g == root)
        elif s.kind == "bcast":
            for grp in groups:
                gather(grp)
        else:
            raise ValueError(f"unknown step kind {s.kind!r}")

    full = frozenset(range(P))
    for r in range(P):
        if plan.op == operation.allreduce:
            want = set(range(P))
        elif plan.op == operation.allgather:
            want = set(range(P))
        else:
            want = {r}
        have = set(state[r])
        if have != want:
            raise ValueError(
                f"rank {r}: final chunks {sorted(have)} != "
                f"required {sorted(want)}")
        for c, (srcs, deliveries) in state[r].items():
            if not gatherish and srcs != full:
                raise ValueError(
                    f"rank {r} chunk {c}: contributions {sorted(srcs)} "
                    f"incomplete")
            if deliveries != 1:
                raise ValueError(
                    f"rank {r} chunk {c}: delivered {deliveries} times")


# ---------------------------------------------------------------------------
# multi-axis program builders — the whole synthesized schedule traced
# into ONE shard_map program (the cmdlist one-launch discipline),
# generalized to N axes and optionally chunk-pipelined
# ---------------------------------------------------------------------------

def _norm_axes(comm, axes) -> Tuple[int, ...]:
    axes = tuple(int(s) for s in axes)
    if len(axes) < 2:
        raise ValueError(f"multiaxis builders need >=2 axes, got {axes}")
    if _prod(axes) != comm.world_size:
        raise ValueError(
            f"{'x'.join(map(str, axes))} != world {comm.world_size}")
    return axes


def _axis_names(nd: int) -> Tuple[str, ...]:
    """Mesh axis names for an N-D program. The 2-D names stay the
    hierarchical pair (stable HLO for the AOT schedule pins); deeper
    declarations extend the family."""
    from .hierarchical import COL_AXIS, ROW_AXIS
    if nd == 2:
        return (ROW_AXIS, COL_AXIS)
    return tuple(f"accl_ax{i}" for i in range(nd))


def _smapnd(comm, axes: Tuple[int, ...], body) -> Callable:
    """jit(reshape -> shard_map over the N-D mesh -> reshape back) — the
    ``_smap2d`` discipline at any rank: ONE compiled launch regardless
    of how many per-axis phases (or pipeline chunks) the body traces."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    names = _axis_names(len(axes))
    mesh = comm.meshnd(axes, names)
    inner = shard_map(
        body, mesh=mesh,
        in_specs=P(*names, None),
        out_specs=P(*names, None),
    )
    world = _prod(axes)

    @jax.jit
    def prog(x):  # x: (world, n) sharded along the 1-D communicator axis
        n = x.shape[-1]
        out = inner(x.reshape(axes + (n,)))
        return out.reshape(world, -1)

    return prog


def _wavefront(parts: list, phases: list) -> list:
    """Trace the per-chunk phase chains in PIPELINED (wavefront) order:
    at wave w, chunk c runs its phase w-c — so chunk c's axis-k+1 leg
    is issued right after chunk c+1's axis-k leg, the instruction order
    XLA's scheduler overlaps (the chunks carry no cross-chunk data
    dependency, so the per-axis collectives of different chunks ride
    their own axes' links concurrently). Order is observable in the
    emitted HLO only; the dataflow — and therefore the result — is
    bit-identical to running each chunk's chain sequentially."""
    states = list(parts)
    n_ph = len(phases)
    for wave in range(n_ph + len(states) - 1):
        for c in range(len(states)):
            k = wave - c
            if 0 <= k < n_ph:
                states[c] = phases[k](states[c])
    return states


def build_multiaxis_allreduce(comm, axes, func: reduceFunction,
                              dt: dataType, arith=None,
                              pipeline_chunks: int = 1) -> Callable:
    """Axis-by-axis torus allreduce over an N-axis declaration:
    reduce-scatter down the last axis, then each previous axis on the
    shrinking shard, then the dual all-gathers back up — 2N per-axis
    XLA collectives over the true N-D mesh, compiled as one launch.
    Per-link traffic N(s−1)/s on the heavy axis (vs N(P−1)/P for the
    flat ring) at Σ(sᵢ−1) hops per sweep.  ``pipeline_chunks`` > 1
    splits the payload into chunks whose phase chains are traced in
    wavefront order (chunk c's axis-k+1 leg beside chunk c+1's axis-k
    leg) — same one-launch program, same bits, overlapped wire time."""
    import jax.numpy as jnp
    from jax import lax

    from .. import ops
    from .primitives import _unwire, _wire, lane_blocks

    axes = _norm_axes(comm, axes)
    nd, world = len(axes), _prod(axes)
    names = _axis_names(nd)
    C = max(1, int(pipeline_chunks))
    decompress_arith = (arith is not None and arith.decompress_before_arith)

    # per-chunk phase chain: phases[0] wires, the middle phases are the
    # per-axis legs, the last unwires — composition is the sequential
    # 2-D body generalized to N axes (scatter LAST axis first, gather
    # back in reverse; bit-identical per element at any chunking)
    def _phases(out_dtype, x_dtype):
        ph = [lambda t: _wire(t, arith)]
        if func == reduceFunction.SUM and not decompress_arith:
            for ax in reversed(range(nd)):
                ph.append(lambda t, ax=ax: lax.psum_scatter(
                    lane_blocks(t, axes[ax]), names[ax],
                    scatter_dimension=0, tiled=False))
            for ax in range(nd):
                ph.append(lambda t, ax=ax: lax.all_gather(
                    t, names[ax], tiled=True))
        elif func == reduceFunction.SUM:
            # decompress-before-arith wires: every hop carries the wire
            # dtype, every fold runs at full precision (per-axis chunk
            # exchange + local fold, the hierarchical discipline)
            ph = []
            for ax in reversed(range(nd)):
                ph.append(lambda t, ax=ax: ops.reduce_axis0(
                    _unwire(lax.all_to_all(
                        lane_blocks(_wire(t, arith), axes[ax]), names[ax],
                        split_axis=0, concat_axis=0), arith, x_dtype),
                    func, dt))
            ph.append(lambda t: lax.all_gather(_wire(t, arith), names[0],
                                               tiled=True))
            for ax in range(1, nd):
                ph.append(lambda t, ax=ax: lax.all_gather(
                    t, names[ax], tiled=True))
        elif func == reduceFunction.MAX:
            # max of wire values == wire of max (monotone cast): exact
            for ax in reversed(range(nd)):
                ph.append(lambda t, ax=ax: lax.pmax(t, names[ax]))
        else:
            raise ValueError(func)
        ph.append(lambda t: _unwire(t, arith, out_dtype))
        return ph

    def body(v):  # (1,)*nd + (n,)
        n = v.shape[-1]
        x = v.reshape(n)
        pad = (-n) % (world * C)
        if pad:
            x = jnp.pad(x, (0, pad))
        phases = _phases(v.dtype, x.dtype)
        parts = list(lane_blocks(x, C)) if C > 1 else [x]
        outs = [o.reshape(-1) for o in _wavefront(parts, phases)]
        out = jnp.concatenate(outs) if C > 1 else outs[0]
        out = out[:n] if pad else out
        return out.reshape((1,) * nd + (n,))

    return _smapnd(comm, axes, body)


def build_multiaxis_reduce_scatter(comm, axes, func: reduceFunction,
                                   dt: dataType, arith=None,
                                   pipeline_chunks: int = 1) -> Callable:
    """Axis-by-axis reduce-scatter: the input's world chunks are
    pre-permuted so the per-axis scatters land rank (r₀, …, rₙ₋₁)
    exactly its FLAT chunk (the row-major rank index) — the 1-D
    convention every caller and the flat-ring path share. Pipeline
    chunks split each rank's OUTPUT block; chunk c folds the strided
    input slice that lands in output piece c."""
    import jax.numpy as jnp
    from jax import lax

    from .. import ops
    from .primitives import LANES, _unwire, _wire, lane_blocks

    axes = _norm_axes(comm, axes)
    nd, world = len(axes), _prod(axes)
    names = _axis_names(nd)
    C = max(1, int(pipeline_chunks))
    decompress_arith = (arith is not None and arith.decompress_before_arith)
    # flat chunk (r0..r_{nd-1}) sits at t[r_{nd-1}, ..., r0] after the
    # reversal below: scattering the LAST axis first then each previous
    # one leaves rank (r0..r_{nd-1}) holding exactly its flat chunk
    perm = tuple(reversed(range(nd))) + (nd,)

    def _permute(t, pc):
        # lanes-minor when the piece splits into whole lanes (lane_blocks)
        if pc % LANES == 0:
            t = t.reshape(axes + (pc // LANES, LANES))
            return lane_blocks(t.transpose(perm + (nd + 1,)), axes[-1])
        return lane_blocks(t.reshape(axes + (pc,)).transpose(perm),
                           axes[-1])

    def _phases(out_dtype, x_dtype, pc):
        prep = [lambda t: _wire(_permute(t, pc), arith)]
        if func == reduceFunction.SUM and not decompress_arith:
            ph = prep
            for ax in reversed(range(nd)):
                ph.append(lambda t, ax=ax: lax.psum_scatter(
                    lane_blocks(t, axes[ax]), names[ax],
                    scatter_dimension=0, tiled=False))
            ph.append(lambda t: _unwire(t, arith, out_dtype))
        else:
            # general path (MAX, decompress-before-arith): per-axis
            # chunk exchange + rank-ordered local fold at full precision
            ph = [lambda t: _permute(t, pc)]
            for ax in reversed(range(nd)):
                ph.append(lambda t, ax=ax: ops.reduce_axis0(
                    _unwire(lax.all_to_all(
                        lane_blocks(_wire(t, arith), axes[ax]), names[ax],
                        split_axis=0, concat_axis=0), arith, x_dtype),
                    func, dt))
            ph.append(lambda t: t.astype(out_dtype))
        return ph

    def body(v):  # (1,)*nd + (world*count,)
        x = v.reshape(-1)
        count = x.shape[-1] // world
        pc = -(-count // C)
        padc = pc * C - count
        t = (jnp.pad(x.reshape(world, count), ((0, 0), (0, padc)))
             if padc else x)
        phases = _phases(v.dtype, x.dtype, pc)
        # chunk c's input: piece c of every rank's destined block
        if C == 1:
            parts = [t]
        elif pc % LANES == 0:
            tc = t.reshape(world, C, pc // LANES, LANES).transpose(1, 0, 2, 3)
            parts = [tc[c] for c in range(C)]
        else:
            tc = t.reshape(world, C, pc).transpose(1, 0, 2)  # (C, world, pc)
            parts = [tc[c] for c in range(C)]
        outs = [o.reshape(-1) for o in _wavefront(parts, phases)]
        out = jnp.concatenate(outs)[:count] if (C > 1 or padc) else outs[0]
        return out.reshape((1,) * nd + (count,))

    return _smapnd(comm, axes, body)


def build_multiaxis_allgather(comm, axes, arith=None,
                              pipeline_chunks: int = 1) -> Callable:
    """Axis-by-axis all-gather (the reduce-scatter dual): gather up
    axis 0, then each next axis, then un-permute so the result is in
    flat chunk order. Pipeline chunks split each rank's input block and
    re-interleave per destination block on the way out."""
    import jax.numpy as jnp
    from jax import lax

    from .primitives import _unwire, _wire, lane_blocks

    axes = _norm_axes(comm, axes)
    nd, world = len(axes), _prod(axes)
    names = _axis_names(nd)
    C = max(1, int(pipeline_chunks))

    def _phases(out_dtype, pc):
        # each piece lanes-minor (lane_blocks): (rows, LANES) or (pc,)
        ph = [lambda t: lax.all_gather(
            _wire(lane_blocks(t, 1)[0], arith), names[0])]
        for ax in range(1, nd):
            ph.append(lambda t, ax=ax: lax.all_gather(t, names[ax]))

        def unpermute(t):
            # gathered leading dims accumulate as (s_{nd-1}, ..., s_0):
            # reverse them so index (r0, ..., r_{nd-1}) flattens to the
            # flat chunk order
            tail = t.ndim - nd
            perm = (tuple(reversed(range(nd)))
                    + tuple(range(nd, nd + tail)))
            t = _unwire(t, arith, out_dtype).transpose(perm)
            return t.reshape((world,) + t.shape[nd:])
        ph.append(unpermute)
        return ph

    def body(v):  # (1,)*nd + (count,) -> (1,)*nd + (world*count,)
        x = v.reshape(-1)
        count = x.shape[-1]
        pc = -(-count // C)
        padc = pc * C - count
        if padc:
            x = jnp.pad(x, (0, padc))
        phases = _phases(v.dtype, pc)
        parts = list(lane_blocks(x, C))
        outs = _wavefront(parts, phases)       # each (world, <piece>)
        if C > 1 or padc:
            out = jnp.stack(outs, axis=1)      # (world, C, <piece>)
            if padc:
                out = out.reshape(world, C * pc)[:, :count]
            out = out.reshape(-1)
        else:
            out = outs[0].reshape(-1)
        return out.reshape((1,) * nd + (world * count,))

    return _smapnd(comm, axes, body)


# ---------------------------------------------------------------------------
# plan inspection CLI — `python -m accl_tpu.parallel.synth --explain ...`
# ---------------------------------------------------------------------------

class _HypotheticalComm:
    """Just enough communicator surface to drive the REAL resolution
    path (``_select_legacy`` + :func:`resolve`) for a topology that is
    not live anywhere: world size, a coordinate-free device list, no
    parent, no shrink mark. ``hosts`` emulates a host-aligned
    multi-slice mesh ((slices, per-slice) from ``hosts_shape``) so DCN
    two-tier decisions are inspectable offline too."""

    def __init__(self, world: int, hosts: Optional[Tuple[int, int]] = None):
        self.world_size = int(world)
        self._devices = [object()] * self.world_size
        self.parent = None
        self.degraded_from = None
        self._hosts = tuple(hosts) if hosts else None

    @property
    def devices(self):
        return list(self._devices)

    def hosts_shape(self):
        return self._hosts


def _explain(op_name: str, nbytes: int, shape: str,
             cfg: ACCLConfig) -> str:
    """The candidate table for one hypothetical (op, payload, topology):
    every generator's plan with its cost split into the α (hops) and β
    (bytes) terms, the argmin marked, and the decision ``resolve()``
    would actually make under ``cfg`` (source and shape) — so a plan
    decision is inspectable without a live session."""
    from . import algorithms

    op = {"allreduce": operation.allreduce,
          "allgather": operation.allgather,
          "reduce_scatter": operation.reduce_scatter}.get(op_name)
    if op is None:
        raise SystemExit(f"unknown op {op_name!r}: use allreduce | "
                         "allgather | reduce_scatter")
    axes = tuple(int(s) for s in shape.lower().split("x"))
    world = _prod(axes)
    on_dcn = cfg.transport == TransportBackend.DCN
    if on_dcn and len(axes) == 2:
        # a 2-D shape on a DCN transport IS the slice split: emulate a
        # host-aligned (slices, per-slice) mesh so the two-tier window
        # (and its per-tier cost split) is inspectable offline
        comm = _HypotheticalComm(world, hosts=axes)
    elif on_dcn and len(axes) > 2:
        # topology_of ignores declared tori on DCN (the slice boundary
        # is physical) — silently pricing a 1-D table under a header
        # claiming the declared shape would mislead; refuse instead
        raise SystemExit(
            "DCN topologies are 2-D (slices x per-slice): declared "
            f"{'x'.join(map(str, axes))} has no DCN interpretation "
            "(N-D tori are ICI declarations)")
    else:
        comm = _HypotheticalComm(world)
        if len(axes) >= 2:
            cfg = cfg.replace(sched_mesh_shape=list(axes))
    topo = topology_of(comm, cfg)
    model = model_for(cfg, topo)
    cands = sorted(candidates(op, topo, nbytes, cfg),
                   key=lambda p: p.predicted_us)
    legacy = algorithms._select_legacy(op, nbytes, comm, cfg)
    plan = resolve(op, nbytes, comm, cfg, legacy)
    tiered = topo.dcn_axis is not None
    param_line = (f"alpha={model.alpha_us}us beta={model.beta_gbps}GB/s "
                  f"pipeline_chunks={cfg.sched_pipeline_chunks} "
                  f"startup={cfg.sched_pipeline_startup_us}us")
    if tiered:
        param_line = (
            f"ici: alpha={model.alpha_us}us beta={model.beta_gbps}GB/s | "
            f"dcn: alpha={model.dcn_alpha_us}us "
            f"beta={model.dcn_beta_gbps}GB/s | "
            f"dcn_wire_dtype={getattr(cfg, 'dcn_wire_dtype', 'off')}")
    lines = [
        f"op={op.name} nbytes={nbytes} topology={'x'.join(map(str, axes))} "
        f"transport={topo.transport.value} "
        f"bidirectional={topo.bidirectional}"
        + (" dcn_axis=0 (slices x per-slice)" if tiered else ""),
        param_line,
        "",
        f"{'shape':<13} {'algorithm':<10} {'steps':>5} {'hops':>5} "
        f"{'alpha_us':>9} {'bw_us':>9} {'total_us':>9}"
        + ("  per-tier split" if tiered else ""),
    ]
    best = cands[0]
    for p in cands:
        hops = sum(s.hops for s in p.steps)
        alpha_us = sum(model.for_transport(s.transport).alpha_us * s.hops
                       for s in p.steps)
        if p.shape == "pipeline":
            # the pipelined cost is NOT the per-step sum — report the
            # makespan split as bottleneck-phase bw + fill cost
            alpha_us = (cfg.sched_pipeline_startup_us
                        * (cfg.sched_pipeline_chunks - 1))
        bw_us = p.predicted_us - alpha_us
        mark = "  <- winner (argmin cost)" if p is best else ""
        label = p.shape
        if p.shape == "twotier":
            label = f"twotier/{p.param('dcn_wire_dtype', 'off')}"
        split = ""
        if tiered and p.shape != "pipeline":
            # the per-tier cost split: which tier the predicted time
            # actually sits on (DCN steps at the dcn α/β, the rest ici)
            dcn_us = sum(
                model.step_us(s.hops, s.link_bytes, s.channels, s.transport)
                for s in p.steps if s.transport == TransportBackend.DCN)
            split = (f"  [ici={p.predicted_us - dcn_us:.2f}us "
                     f"dcn={dcn_us:.2f}us]")
        lines.append(
            f"{label:<13} {p.algorithm.value:<10} {len(p.steps):>5} "
            f"{hops:>5} {alpha_us:>9.2f} {bw_us:>9.2f} "
            f"{p.predicted_us:>9.2f}{mark}{split}")
    lines += [
        "",
        f"legacy ladder decision: {legacy.value}",
        f"resolve() decision:     shape={plan.shape} "
        f"algorithm={plan.algorithm.value} source={plan.source} "
        f"~{plan.predicted_us:.2f}us",
        f"  {plan.describe()}",
    ]
    return "\n".join(lines)


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m accl_tpu.parallel.synth",
        description="Inspect schedule-synthesis decisions for a "
                    "hypothetical topology (no live session needed).")
    ap.add_argument("--explain", action="store_true", required=True,
                    help="print the candidate table, cost breakdown and "
                         "resolve() decision")
    ap.add_argument("op", help="allreduce | allgather | reduce_scatter")
    ap.add_argument("nbytes", type=int,
                    help="payload bytes in the op's select() convention")
    ap.add_argument("shape",
                    help="topology, e.g. 8 (single axis), 2x4, 2x2x2")
    ap.add_argument("--transport", default="sim",
                    choices=["sim", "ici", "dcn"])
    ap.add_argument("--chunks", type=int, default=None,
                    help="override sched_pipeline_chunks")
    ap.add_argument("--startup-us", type=float, default=None,
                    help="override sched_pipeline_startup_us")
    ap.add_argument("--alpha-us", type=float, default=None)
    ap.add_argument("--beta-gbps", type=float, default=None)
    ap.add_argument("--full-authority", action="store_true",
                    help="resolve under cfg.sched_full_authority")
    args = ap.parse_args(argv)
    cfg = ACCLConfig(transport=TransportBackend(args.transport))
    if args.chunks is not None:
        cfg = cfg.replace(sched_pipeline_chunks=args.chunks)
    if args.startup_us is not None:
        cfg = cfg.replace(sched_pipeline_startup_us=args.startup_us)
    if args.alpha_us is not None:
        cfg = cfg.replace(sched_alpha_us=args.alpha_us)
    if args.beta_gbps is not None:
        cfg = cfg.replace(sched_beta_gbps=args.beta_gbps)
    if args.full_authority:
        cfg = cfg.replace(sched_full_authority=True)
    print(_explain(args.op, args.nbytes, args.shape, cfg))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
