"""Pipeline parallelism (pp): 1F1B scheduling with a Pallas-overlapped
activation relay, composed with the fused tp/dp datapaths.

Two generations of the same idea live here (the ``models/zero.py``
shape):

* the original **GPipe demo** (:func:`build_pipeline_forward` /
  :func:`build_gpipe_train_step`): all ``M`` forwards, then all ``M``
  backwards, ``M + N - 1`` lockstep ticks per phase with activations
  hopping rank-to-rank via ``ppermute``.  Bubble steps are genuinely
  SKIPPED under ``lax.cond`` (they used to compute on zeros and mask
  after the fact — the A/B against 1F1B now measures schedule cost,
  not wasted-FLOP cost).  It remains the parity oracle and the honest
  committed fallback of the composed step;
* the **1F1B step** (:func:`build_pp_train_step`), one-forward-one-
  backward scheduling (PipeDream-flush / Megatron): after a short
  warmup every stage alternates forward and backward work, so
  steady-state activation memory drops from O(M) stashed microbatches
  to O(world) — the stash buffer is literally ``(world, n, d)``,
  asserted on traced shapes — and the bubble fraction from
  ``(world-1)/(M+world-1)`` per phase to the ``(world-1)/M`` class.
  Optional **interleaved virtual stages** (``pp_interleave = V``): rank
  ``r`` owns stages ``r, r+S, ...``, cutting the fill bubble ~1/V at
  ``world`` stash slots per virtual chunk.

The whole 1F1B schedule runs as ONE jitted ``shard_map`` program with
static shapes: a host-side lockstep simulator (:func:`schedule_table`)
emits per-tick work tables (which microbatch/chunk each rank forwards
or backwards, which stash slot it touches), and the train step is a
masked ``lax.scan`` over those tables — bubble ticks take the empty
``lax.cond`` branch, so no stage matmul ever runs on zeros.  Every tick
relays two payloads at once — microbatch i's forward activation one
stage ahead, microbatch i-k's gradient one stage back — through
:func:`accl_tpu.ops.pipeline_relay.pp_relay`: the double-buffered
credit-semaphore Pallas kernel when its plan engages, the counted
``ppermute`` fallback otherwise
(``accl_cmatmul_fallback_total{op="pp_relay"}``).

**Composition** (:func:`build_pp_transformer_train_step`): a
(pp, dp, tp) mesh whose per-stage block is the existing fused family —
flash attention, the agmm/mmrs MLP over dp with ZeRO-sharded
travel-layout stage parameters, the bucket-gather attention leg — i.e.
one ``models/zero.py`` transformer block per pipeline stage, scheduled
1F1B along pp.  Commit-honesty follows the zero discipline: the fused
datapath runs only when EVERY per-stage plan engages (relay plan +
:func:`~accl_tpu.models.zero.fsdp_engage_reason`); any decline falls
back WHOLE to the GPipe baseline schedule with the flat datapath,
counted under ``accl_cmatmul_fallback_total{op="pp_pipeline"}`` (an
explicit ``overlap=False`` is a requested baseline — the 1F1B schedule
still runs, unfused and uncounted).

**Cross-axis arbitration**: ``pp_schedule="auto"`` resolves through the
round-12 α-β cost model (:func:`resolve_pp_schedule`): the relay's wire
time and the tp collective's link occupancy are priced jointly per tick
(``parallel/synth.link_cost_us``) and the schedule with the lower
predicted total wins, counted under
``accl_sched_plan_total{op="pipeline", source=...}``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ..communicator import Communicator
from ..obs import metrics as _metrics
from ..parallel.primitives import AXIS, _smap
from ..parallel.ring import _fwd_perm

PP_AXIS = "pp"

#: the fallback-counter op label of the composed step's committed
#: baseline (accl_cmatmul_fallback_total{op="pp_pipeline"})
PP_STEP_OP = "pp_pipeline"


# ---------------------------------------------------------------------------
# session registers (ACCLConfig.pp_schedule / pp_interleave write-through,
# the zero_overlap shape); per-call override on every builder.  The relay's
# pp_overlap register lives with its kernel (ops/pipeline_relay.py).
# ---------------------------------------------------------------------------

_SCHEDULE_DEFAULT = "auto"
_INTERLEAVE_DEFAULT = 1
_COST_CFG = None  # ACCLConfig the "auto" arbiter prices with (None=defaults)


def set_schedule(schedule: str) -> None:
    """Module-default schedule (``ACCLConfig.pp_schedule`` lands here on
    every config assignment): "auto" (cost-model arbitration), "1f1b",
    or "gpipe". Per-call override: the builders' ``schedule`` argument."""
    if schedule not in ("auto", "1f1b", "gpipe"):
        raise ValueError(f"pp_schedule must be auto|1f1b|gpipe, "
                         f"got {schedule!r}")
    global _SCHEDULE_DEFAULT
    _SCHEDULE_DEFAULT = schedule


def get_schedule() -> str:
    return _SCHEDULE_DEFAULT


def set_interleave(v: int) -> None:
    """Module-default virtual-stage count (``ACCLConfig.pp_interleave``
    write-through)."""
    if int(v) < 1:
        raise ValueError(f"pp_interleave must be >= 1, got {v}")
    global _INTERLEAVE_DEFAULT
    _INTERLEAVE_DEFAULT = int(v)


def get_interleave() -> int:
    return _INTERLEAVE_DEFAULT


def set_cost_config(cfg) -> None:
    """Give the "auto" arbiter the session's cost registers (α/β,
    pipeline chunks) — ACCL's config write-through calls this with every
    assignment, like ``zero.set_overlap_enabled``."""
    global _COST_CFG
    _COST_CFG = cfg


# ===========================================================================
# the 1F1B schedule table — a host-side lockstep simulator
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class PPSchedule:
    """Static per-tick work tables for the 1F1B masked scan.

    All tables are (steps, world) int32 with -1 meaning "none".  At tick
    ``t`` rank ``r``:

    * banks the forward payload that arrived on the wire into activation
      stash slot ``arr_f_slot[t, r]`` and the gradient payload into
      grad-landing slot ``arr_b_slot[t, r]``;
    * forwards microbatch ``f_mb[t, r]`` of virtual chunk ``f_chunk``,
      reading/stashing its input at ``f_slot`` (the slot the arrival was
      banked into; injections at stage 0 allocate it here) — the LAST
      stage also writes the loss gradient into ``dy_slot``;
    * backwards ``b_mb``/``b_chunk``, consuming activation slot
      ``b_slot`` and gradient slot ``b_in_slot`` (both freed).

    ``stash_slots`` bounds the live activations per rank: ``world`` for
    the plain schedule (THE 1F1B memory claim), ``world`` per virtual
    chunk when interleaved.  ``max_live`` is the simulator's measured
    high-water mark (``<= stash_slots`` by construction)."""

    world: int
    n_micro: int
    interleave: int
    steps: int
    stash_slots: int
    grad_slots: int
    f_mb: np.ndarray
    f_chunk: np.ndarray
    f_slot: np.ndarray
    dy_slot: np.ndarray
    b_mb: np.ndarray
    b_chunk: np.ndarray
    b_slot: np.ndarray
    b_in_slot: np.ndarray
    arr_f_slot: np.ndarray
    arr_b_slot: np.ndarray
    max_live: int

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the schedule: every rank does ``2*M*V`` work
        units in ``steps`` lockstep ticks."""
        busy = 2 * self.n_micro * self.interleave
        return 1.0 - busy / self.steps


def gpipe_bubble_fraction(world: int, n_micro: int,
                          interleave: int = 1) -> float:
    """The GPipe baseline's bubble fraction at the same geometry: each
    phase is ``M + N - 1`` ticks for ``M`` busy ones (N = world *
    interleave stages)."""
    N = world * interleave
    return 1.0 - n_micro / (n_micro + N - 1)


def validate_pp_geometry(world: int, n_micro: int,
                         interleave: int = 1) -> None:
    """The 1F1B schedule needs at least ``world`` microbatches: with
    ``M < world`` some stages never reach steady state and the bubble
    mask cannot cover the degenerate schedule (the old GPipe demo
    silently computed garbage there).  Fail loud instead."""
    if n_micro < world:
        raise ValueError(
            f"1F1B needs n_micro >= world: got n_micro={n_micro} for "
            f"world={world}. Use more microbatches or "
            f"schedule=\"gpipe\" (the baseline handles any M >= 1).")
    if interleave < 1:
        raise ValueError(f"interleave must be >= 1, got {interleave}")


@functools.lru_cache(maxsize=64)
def schedule_table(world: int, n_micro: int,
                   interleave: int = 1) -> PPSchedule:
    """Simulate the 1F1B lockstep schedule and emit its static tables.
    Memoized per geometry — the "auto" arbiter and the step builder
    both consult the same table, and the tables are frozen
    (callers must not mutate the arrays).

    Rank-local policy per tick (the PipeDream-flush discipline):
    **backward first** whenever one is ready, else the lowest
    (microbatch, chunk) forward whose input has arrived — with stage-0
    injections gated on the global in-flight count staying <= ``world``
    (that gate IS the O(world) activation bound; everything downstream
    inherits it by conservation).  Payloads relay one ring hop per tick
    (+1 forward, -1 backward) and land the next tick.

    Raises on geometry the masked scan cannot cover (``M < world``)."""
    validate_pp_geometry(world, n_micro, interleave)
    S, V, M = world, interleave, n_micro
    N = S * V
    # simulate with a can't-overflow buffer (total in-flight <= M*V) and
    # SIZE the stash to the measured high-water mark afterwards: the
    # lowest-free allocation policy keeps every allocated index strictly
    # below the occupancy peak, so the tables stay valid for the tight
    # buffer.  The injection gate (<= N in-flight microbatches) bounds
    # that peak at ``world`` for the plain schedule.
    sim_slots = M * V
    free_act = [list(range(sim_slots)) for _ in range(S)]
    free_inb = [list(range(sim_slots)) for _ in range(S)]
    act_slot_of = [dict() for _ in range(S)]   # (m, c) -> stash slot
    inb_slot_of = [dict() for _ in range(S)]   # (m, c) -> grad slot
    ready_f = [[] for _ in range(S)]           # (m, c) input present
    ready_b = [[] for _ in range(S)]           # [(ready_tick, m, c)]
    arrivals: list = []                        # (tick, kind, rank, m, c)
    for m in range(M):
        ready_f[0].append((m, 0))
    injected = drained = 0
    done_b = 0
    max_live = max_live_inb = 0
    rows: list = []
    hard_cap = 6 * (M * V + N) + 32
    t = 0
    while done_b < M * N:
        if t >= hard_cap:
            raise RuntimeError(
                f"1F1B simulator did not converge (world={S}, M={M}, "
                f"V={V}) — internal scheduling bug")
        row = {k: [-1] * S for k in
               ("f_mb", "f_chunk", "f_slot", "dy_slot", "b_mb",
                "b_chunk", "b_slot", "b_in_slot", "arr_f_slot",
                "arr_b_slot")}
        # 1) land this tick's wire arrivals (at most one per direction
        #    per rank: each neighbor produced at most one payload)
        frees: list = []
        for ev in [e for e in arrivals if e[0] == t]:
            _, kind, r, m, c = ev
            if kind == "f":
                if not free_act[r]:
                    raise RuntimeError("activation stash overflow — "
                                       "injection gate bug")
                s = free_act[r].pop(0)
                act_slot_of[r][(m, c)] = s
                row["arr_f_slot"][r] = s
                ready_f[r].append((m, c))
            else:
                if not free_inb[r]:
                    raise RuntimeError("gradient landing overflow")
                s = free_inb[r].pop(0)
                inb_slot_of[r][(m, c)] = s
                row["arr_b_slot"][r] = s
                ready_b[r].append((t, m, c))
        arrivals = [e for e in arrivals if e[0] > t]

        # 2) one work unit per rank: backward first (1F1B), else the
        #    lowest-(mb, chunk) available forward
        for r in range(S):
            bs = sorted((e for e in ready_b[r] if e[0] <= t),
                        key=lambda e: (e[1], e[2]))
            if bs:
                _, m, c = bs[0]
                ready_b[r].remove(next(e for e in ready_b[r]
                                       if e[1:] == (m, c)))
                sig = c * S + r
                a_slot = act_slot_of[r].pop((m, c))
                g_slot = inb_slot_of[r].pop((m, c))
                row["b_mb"][r], row["b_chunk"][r] = m, c
                row["b_slot"][r], row["b_in_slot"][r] = a_slot, g_slot
                frees.append((free_act[r], a_slot))
                frees.append((free_inb[r], g_slot))
                if sig > 0:
                    pr, pc = (r - 1, c) if r > 0 else (S - 1, c - 1)
                    arrivals.append((t + 1, "b", pr, m, pc))
                else:
                    drained += 1
                done_b += 1
                continue
            fs = sorted(ready_f[r])
            for m, c in fs:
                sig = c * S + r
                if sig == 0:
                    # injection allocates a stash slot: gate on the
                    # global in-flight bound — ``world`` microbatches
                    # for the plain schedule (the O(world) claim), one
                    # per stage when interleaved (the pipe needs N
                    # in-flight to fill N stages)
                    if injected - drained >= N or not free_act[r]:
                        continue
                    s = free_act[r].pop(0)
                    act_slot_of[r][(m, c)] = s
                    injected += 1
                else:
                    s = act_slot_of[r][(m, c)]
                ready_f[r].remove((m, c))
                row["f_mb"][r], row["f_chunk"][r] = m, c
                row["f_slot"][r] = s
                if sig == N - 1:
                    # the last stage turns the microbatch around: the
                    # loss gradient lands locally like a wire arrival
                    if not free_inb[r]:
                        raise RuntimeError("gradient landing overflow")
                    g = free_inb[r].pop(0)
                    inb_slot_of[r][(m, c)] = g
                    row["dy_slot"][r] = g
                    ready_b[r].append((t + 1, m, c))
                else:
                    nr, nc = (r + 1, c) if r < S - 1 else (0, c + 1)
                    arrivals.append((t + 1, "f", nr, m, nc))
                break
        # 3) measure the within-tick occupancy PEAK (before frees land:
        #    a slot allocated and freed inside one tick was still live),
        #    then release — a slot freed by B is reusable by the NEXT
        #    tick's arrival, matching the scan's write order
        max_live = max(max_live,
                       *(sim_slots - len(free_act[r]) for r in range(S)))
        max_live_inb = max(max_live_inb,
                           *(sim_slots - len(free_inb[r])
                             for r in range(S)))
        for lst, s in frees:
            lst.append(s)
            lst.sort()
        rows.append(row)
        t += 1

    T = len(rows)
    tab = {k: np.array([row[k] for row in rows], np.int32)
           for k in rows[0]}
    slots = max(max_live, 1)
    if V == 1:
        # THE 1F1B memory claim — the scan's stash buffer is (world,
        # n, d), never O(M)
        assert slots <= S, (slots, S)
    return PPSchedule(world=S, n_micro=M, interleave=V, steps=T,
                      stash_slots=slots, grad_slots=max(max_live_inb, 1),
                      max_live=max_live,
                      f_mb=tab["f_mb"], f_chunk=tab["f_chunk"],
                      f_slot=tab["f_slot"], dy_slot=tab["dy_slot"],
                      b_mb=tab["b_mb"], b_chunk=tab["b_chunk"],
                      b_slot=tab["b_slot"], b_in_slot=tab["b_in_slot"],
                      arr_f_slot=tab["arr_f_slot"],
                      arr_b_slot=tab["arr_b_slot"])


# ---------------------------------------------------------------------------
# schedule arbitration — the round-12 cost model prices pp against GPipe
# ---------------------------------------------------------------------------


def resolve_pp_schedule(schedule: Optional[str], world: int, n_micro: int,
                        payload_bytes: int, interleave: int = 1,
                        tp: int = 1, tp_bytes: int = 0,
                        transport: str = "ici") -> Tuple[str, str]:
    """THE schedule decision for one pipeline build: ``(schedule,
    source)`` with source in {"register", "cost_model", "degenerate"},
    counted under ``accl_sched_plan_total{op="pipeline"}``.

    ``schedule=None`` follows the session ``ACCLConfig.pp_schedule``
    register; an explicit "1f1b"/"gpipe" (per-call or session) pins the
    decision (source "register").  "auto" arbitrates through the α-β
    cost model: per-tick link occupancy is the pipeline relay AND the
    stage's tp collective priced JOINTLY (``synth.link_cost_us``) — the
    1F1B tick pays ``max(relay, tp)`` (the relay hides under the stage's
    tp collective + compute, both directions of each pp link in one
    kernel) while the GPipe tick pays their sum (two ppermutes XLA may
    or may not overlap) — times each schedule's tick count.  ``M <
    world`` is degenerate for 1F1B (see :func:`validate_pp_geometry`)
    and resolves "gpipe" with source "degenerate"."""
    req = schedule if schedule is not None else _SCHEDULE_DEFAULT
    if req not in ("auto", "1f1b", "gpipe"):
        raise ValueError(
            f"schedule must be auto|1f1b|gpipe, got {req!r}")
    if req in ("1f1b", "gpipe"):
        decision, source = req, "register"
    elif n_micro < world:
        decision, source = "gpipe", "degenerate"
    else:
        from ..parallel import synth
        cfg = _COST_CFG
        if cfg is None:
            from ..config import ACCLConfig
            cfg = ACCLConfig()
        # ONE fused 1F1B tick moves a FULL payload in EACH direction of
        # the link concurrently, so its wire time is one direction's
        # full-payload time (channels=1 — the win is two hops for the
        # price of one, not half the bytes); a GPipe tick moves one
        # payload on one direction (its phases separate the directions)
        relay_us = synth.link_cost_us(cfg, transport, payload_bytes)
        tp_us = (synth.link_cost_us(cfg, transport, tp_bytes,
                                    hops=max(tp - 1, 1))
                 if tp > 1 and tp_bytes else 0.0)
        N = world * interleave
        t_1f1b = schedule_table(world, n_micro, interleave).steps \
            * max(relay_us, tp_us)
        t_gpipe = 2 * (n_micro + N - 1) * (relay_us + tp_us)
        decision = "1f1b" if t_1f1b <= t_gpipe else "gpipe"
        source = "cost_model"
    _metrics.inc("accl_sched_plan_total",
                 labels=(("op", "pipeline"), ("shape", decision),
                         ("source", source)))
    return decision, source


# ===========================================================================
# the original GPipe demo (kept: parity oracle + committed fallback)
# ===========================================================================


class StageParams(NamedTuple):
    w: jax.Array  # (world, d, d) — stage-sharded
    b: jax.Array  # (world, d)


def init_params(key, comm: Communicator, d_model: int) -> StageParams:
    kw, _ = jax.random.split(key)
    scale = (1.0 / d_model) ** 0.5
    return StageParams(
        w=jax.random.normal(kw, (comm.world_size, d_model, d_model),
                            jnp.float32) * scale,
        b=jnp.zeros((comm.world_size, d_model), jnp.float32),
    )


def shard_params(params: StageParams, comm: Communicator) -> StageParams:
    from jax.sharding import PartitionSpec as P
    return StageParams(
        w=jax.device_put(params.w, comm.sharding(P(AXIS, None, None))),
        b=jax.device_put(params.b, comm.sharding(P(AXIS, None))),
    )


def _stage(w, b, h):
    return jax.nn.relu(h @ w + b)


def build_pipeline_forward(comm: Communicator, n_micro: int) -> Callable:
    """Compile the GPipe forward over the communicator's ranks as stages.

    Input x: (world, M, n, d) with rank 0's shard carrying the real
    microbatches (other shards ignored); output (world, M, n, d) with the
    results in rank world-1's shard (other shards zero).  Bubble steps
    take the empty ``lax.cond`` branch — the stage matmul is genuinely
    skipped, not computed on zeros and masked after the fact, so a
    schedule A/B against 1F1B measures schedule cost, not wasted FLOPs.
    """
    world = comm.world_size
    perm = _fwd_perm(world)
    steps = n_micro + world - 1

    def body(params: StageParams, x):
        w, b = params.w[0], params.b[0]            # my stage's weights
        x = x[0]                                   # (M, n, d); rank0's real
        rank = lax.axis_index(AXIS)
        M, n, d = x.shape
        if M != n_micro:  # trace-time shape constant — fail loud, not zeros
            raise ValueError(
                f"input has {M} microbatches but the pipeline was compiled "
                f"for n_micro={n_micro}")

        def step(carry, s):
            h, out = carry
            # rank 0 injects microbatch s; other ranks consume what
            # arrived from the previous rank
            mb = jnp.clip(s, 0, M - 1)
            inject = lax.dynamic_index_in_dim(x, mb, axis=0, keepdims=False)
            inject = jnp.where(s < M, inject, jnp.zeros_like(inject))
            h = jnp.where(rank == 0, inject, h)
            # my microbatch index at step s is s - rank; bubble steps
            # (my_mb outside [0, M)) skip the stage compute entirely
            my_mb = s - rank
            live = (my_mb >= 0) & (my_mb < M)
            y = lax.cond(live, lambda hh: _stage(w, b, hh),
                         lambda hh: jnp.zeros_like(hh), h)
            # the last stage banks finished microbatches into the output
            slot = jnp.clip(my_mb, 0, M - 1)
            banked = lax.dynamic_update_index_in_dim(
                out, y, slot, axis=0)
            out = jnp.where((rank == world - 1) & live, banked, out)
            # relay every activation one stage forward (the ring hop)
            h = lax.ppermute(y, AXIS, perm)
            return (h, out), None

        h0 = jnp.zeros((n, d), x.dtype)
        out0 = jnp.zeros((M, n, d), x.dtype)
        (_, out), _ = lax.scan(step, (h0, out0), jnp.arange(steps))
        return out[None]

    from jax.sharding import PartitionSpec as P
    specs = StageParams(w=P(AXIS, None, None), b=P(AXIS, None))
    return _smap(comm, body, 2,
                 in_specs=(specs, P(AXIS, None, None, None)))


def reference_pipeline(params: StageParams, x: np.ndarray) -> np.ndarray:
    """Host reference: the stages applied sequentially to each microbatch."""
    w = np.asarray(params.w, np.float64)
    b = np.asarray(params.b, np.float64)
    h = x.astype(np.float64)                       # (M, n, d)
    for s in range(w.shape[0]):
        h = np.maximum(h @ w[s] + b[s], 0.0)
    return h


# ===========================================================================
# stage parameters for the TRAIN steps (V virtual chunks per rank)
# ===========================================================================


class PPStageParams(NamedTuple):
    """Per-rank virtual-chunk stacks: rank r owns stages r, r+S, ...
    (chunk-major stage order sigma = chunk * world + rank)."""

    w: jax.Array  # (world, V, d, d)
    b: jax.Array  # (world, V, d)


def init_stage_params(key, comm: Communicator, d_model: int,
                      interleave: int = 1) -> PPStageParams:
    kw, _ = jax.random.split(key)
    scale = (1.0 / d_model) ** 0.5
    return PPStageParams(
        w=jax.random.normal(
            kw, (comm.world_size, interleave, d_model, d_model),
            jnp.float32) * scale,
        b=jnp.zeros((comm.world_size, interleave, d_model), jnp.float32),
    )


def shard_stage_params(params: PPStageParams,
                       comm: Communicator) -> PPStageParams:
    from jax.sharding import PartitionSpec as P
    return PPStageParams(
        w=jax.device_put(params.w, comm.sharding(P(AXIS, None, None, None))),
        b=jax.device_put(params.b, comm.sharding(P(AXIS, None, None))),
    )


def reference_train_loss(params: PPStageParams, x: np.ndarray,
                         y: np.ndarray) -> float:
    """Host oracle for ONE train-step loss: stages applied in chunk-major
    order (sigma = c*S + r), mean over microbatches of the per-microbatch
    MSE."""
    w = np.asarray(params.w, np.float64)   # (S, V, d, d)
    b = np.asarray(params.b, np.float64)
    S, V = w.shape[0], w.shape[1]
    h = x.astype(np.float64)               # (M, n, d)
    for c in range(V):
        for r in range(S):
            h = np.maximum(h @ w[r, c] + b[r, c], 0.0)
    return float(np.mean((h - y.astype(np.float64)) ** 2))


# ---------------------------------------------------------------------------
# the masked-scan slot discipline — ONE copy shared by the simple and
# composed 1F1B scans (a fix to the clip/where guard must hit both)
# ---------------------------------------------------------------------------


def _slot_update(buf, val, slot):
    """``buf[slot] = val`` when ``slot >= 0`` (traced slot; -1 = no-op)."""
    written = lax.dynamic_update_index_in_dim(
        buf, val, jnp.clip(slot, 0, buf.shape[0] - 1), axis=0)
    return jnp.where(slot >= 0, written, buf)


def _slot_read(buf, slot):
    return lax.dynamic_index_in_dim(
        buf, jnp.clip(slot, 0, buf.shape[0] - 1), axis=0, keepdims=False)


# ===========================================================================
# the 1F1B train step (pp-only flagship of the simple stage family)
# ===========================================================================


def build_pp_train_step(comm: Communicator, n_micro: int, d_model: int,
                        lr: float = 1e-2, *,
                        schedule: Optional[str] = None,
                        interleave: Optional[int] = None,
                        overlap: Optional[bool] = None) -> Callable:
    """``step(params, x, y) -> (params, loss)`` — one jitted pipeline
    train step over the communicator's ranks as stages.

    ``x``/``y``: (world, M, n, d) global arrays; rank 0's shard carries
    the microbatches, rank world-1's the targets (other shards ignored).
    ``params``: :class:`PPStageParams` (V virtual chunks per rank).
    Loss = mean over microbatches of the per-microbatch MSE; SGD update.

    ``schedule=None`` follows ``ACCLConfig.pp_schedule`` (through
    :func:`resolve_pp_schedule` when "auto"); "1f1b" requires
    ``n_micro >= world`` (:func:`validate_pp_geometry` — the degenerate
    schedule raises instead of silently computing garbage).  The 1F1B
    arm runs the masked-scan schedule with the per-tick relay riding
    :func:`~accl_tpu.ops.pipeline_relay.pp_relay` (``overlap`` as
    there); "gpipe" builds :func:`build_gpipe_train_step`'s program.

    The returned step carries its resolution on attributes:
    ``.schedule``, ``.decision_source``, ``.table`` (None for gpipe),
    ``.stash_slots``."""
    world = comm.world_size
    V = _INTERLEAVE_DEFAULT if interleave is None else int(interleave)
    # the arbiter prices a per-row payload (the row count is a call-time
    # shape; both schedules scale identically with it)
    decision, source = resolve_pp_schedule(
        schedule, world, n_micro, payload_bytes=4 * d_model,
        interleave=V)
    if decision == "gpipe":
        step = build_gpipe_train_step(comm, n_micro, d_model, lr,
                                      interleave=V)
        step.schedule, step.decision_source = "gpipe", source
        step.table, step.stash_slots = None, n_micro
        return step
    validate_pp_geometry(world, n_micro, V)
    tab = schedule_table(world, n_micro, V)
    T, slots, gslots = tab.steps, tab.stash_slots, tab.grad_slots
    f_mb = jnp.asarray(tab.f_mb)
    f_chunk = jnp.asarray(tab.f_chunk)
    f_slot = jnp.asarray(tab.f_slot)
    dy_slot = jnp.asarray(tab.dy_slot)
    b_mb = jnp.asarray(tab.b_mb)
    b_chunk = jnp.asarray(tab.b_chunk)
    b_slot = jnp.asarray(tab.b_slot)
    b_in_slot = jnp.asarray(tab.b_in_slot)
    arr_f = jnp.asarray(tab.arr_f_slot)
    arr_b = jnp.asarray(tab.arr_b_slot)
    M = n_micro

    from ..ops import pipeline_relay as _relay

    def body(params: PPStageParams, x, y):
        w, bb = params.w[0], params.b[0]      # (V, d, d), (V, d)
        x, y = x[0], y[0]                     # (M, n, d) local shards
        r = lax.axis_index(AXIS)
        _, n, d = x.shape
        dtype = x.dtype

        upd, at = _slot_update, _slot_read

        def tick(carry, t):
            acts, inb, f_wire, b_wire, gw, gb, loss_vec = carry
            # 1) land the payloads relayed in during the previous tick
            acts = upd(acts, f_wire, arr_f[t, r])
            inb = upd(inb, b_wire, arr_b[t, r])

            # 2) forward work (bubble ticks take the empty branch — the
            #    stage matmul is genuinely skipped, never run on zeros)
            fm, fc, fs, ds = f_mb[t, r], f_chunk[t, r], f_slot[t, r], \
                dy_slot[t, r]

            def do_f(ops):
                acts, inb, loss_vec = ops
                mb = jnp.clip(fm, 0, M - 1)
                inject = (r == 0) & (fc == 0)
                h_in = jnp.where(
                    inject,
                    lax.dynamic_index_in_dim(x, mb, 0, keepdims=False),
                    at(acts, fs))
                acts = upd(acts, h_in, fs)       # stash for the backward
                wc, bc_ = at(w, fc), at(bb, fc)
                h_out = _stage(wc, bc_, h_in)
                # last stage: bank the loss, turn the gradient around
                y_m = lax.dynamic_index_in_dim(y, mb, 0, keepdims=False)
                diff = (h_out - y_m).astype(jnp.float32)
                l = jnp.mean(diff * diff)
                loss_vec = jnp.where(
                    ds >= 0,
                    lax.dynamic_update_index_in_dim(loss_vec, l, mb, 0),
                    loss_vec)
                dy = (2.0 / (n * d * M)) * diff
                inb = upd(inb, dy.astype(dtype), ds)
                f_send = jnp.where(ds >= 0, jnp.zeros_like(h_out), h_out)
                return acts, inb, loss_vec, f_send

            acts, inb, loss_vec, f_send = lax.cond(
                fm >= 0, do_f,
                lambda ops: (ops[0], ops[1], ops[2],
                             jnp.zeros((n, d), dtype)),
                (acts, inb, loss_vec))

            # 3) backward work (recompute-from-stash: only the input was
            #    kept — the O(world) memory claim)
            bm, bc, bs, bis = b_mb[t, r], b_chunk[t, r], b_slot[t, r], \
                b_in_slot[t, r]

            def do_b(ops):
                gw, gb = ops
                h_in = at(acts, bs)
                dy = at(inb, bis).astype(jnp.float32)
                wc, bc_ = at(w, bc), at(bb, bc)
                pre = h_in @ wc + bc_
                dpre = dy * (pre > 0)
                ci = jnp.clip(bc, 0, V - 1)
                gw = lax.dynamic_update_index_in_dim(
                    gw, at(gw, bc) + (h_in.astype(jnp.float32).T @ dpre),
                    ci, axis=0)
                gb = lax.dynamic_update_index_in_dim(
                    gb, at(gb, bc) + dpre.sum(0), ci, axis=0)
                dh = dpre @ wc.T
                first = (r == 0) & (bc == 0)
                b_send = jnp.where(first, jnp.zeros_like(dh), dh)
                return gw, gb, b_send.astype(dtype)

            gw, gb, b_send = lax.cond(
                bm >= 0, do_b,
                lambda ops: (ops[0], ops[1], jnp.zeros((n, d), dtype)),
                (gw, gb))

            # 4) the relay: microbatch i's forward activation and
            #    microbatch i-k's gradient ride ONE fused bidirectional
            #    hop (Pallas kernel when the plan engages; counted
            #    ppermute fallback otherwise)
            f_wire, b_wire = _relay.pp_relay(f_send, b_send, AXIS,
                                             (AXIS,), overlap)
            return (acts, inb, f_wire, b_wire, gw, gb, loss_vec), None

        acts0 = jnp.zeros((slots, n, d), dtype)      # THE stash: O(world)
        inb0 = jnp.zeros((gslots, n, d), dtype)
        gw0 = jnp.zeros((V, d, d), jnp.float32)
        gb0 = jnp.zeros((V, d), jnp.float32)
        wire0 = jnp.zeros((n, d), dtype)
        carry0 = (acts0, inb0, wire0, wire0, gw0, gb0,
                  jnp.zeros((M,), jnp.float32))
        carry, _ = lax.scan(tick, carry0, jnp.arange(T))
        _, _, _, _, gw, gb, loss_vec = carry
        # per-mb losses live on the last stage's rank; replicate
        loss = lax.psum(jnp.sum(loss_vec), AXIS) / M
        w_new = w - lr * gw.astype(w.dtype)
        b_new = bb - lr * gb.astype(bb.dtype)
        return w_new[None], b_new[None], loss

    from jax.sharding import PartitionSpec as P
    specs = PPStageParams(w=P(AXIS, None, None, None),
                          b=P(AXIS, None, None))
    prog = _smap(comm, body, 3,
                 in_specs=(specs, P(AXIS, None, None, None),
                           P(AXIS, None, None, None)),
                 out_specs=(P(AXIS, None, None, None),
                            P(AXIS, None, None), P()))

    def step(params: PPStageParams, x, y):
        w, b, loss = prog(params, x, y)
        return PPStageParams(w, b), loss

    step.schedule, step.decision_source = "1f1b", source
    step.table, step.stash_slots = tab, slots
    return step


# ---------------------------------------------------------------------------
# the GPipe train step — the parity oracle and committed fallback
# ---------------------------------------------------------------------------


def build_gpipe_train_step(comm: Communicator, n_micro: int, d_model: int,
                           lr: float = 1e-2, *,
                           interleave: int = 1) -> Callable:
    """``step(params, x, y) -> (params, loss)`` — the GPipe baseline:
    all-forward-then-all-backward via ``jax.value_and_grad`` through the
    cond-skipped forward scan.  Stashes all ``M`` microbatch activations
    (the scan's saved residuals) — the memory the 1F1B schedule's
    O(world) stash is measured against.  Handles any ``n_micro >= 1``
    (it IS the fallback for the degenerate ``M < world`` geometry)."""
    world = comm.world_size
    V = int(interleave)
    if n_micro < 1:
        raise ValueError(f"n_micro must be >= 1, got {n_micro}")
    N = world * V
    M = n_micro
    steps = M + N - 1

    def body(params: PPStageParams, x, y):
        w, bb = params.w[0], params.b[0]      # (V, d, d), (V, d)
        x, y = x[0], y[0]                     # (M, n, d)
        r = lax.axis_index(AXIS)
        _, n, d = x.shape
        perm = _fwd_perm(world)

        def loss_fn(wb):
            w, bb = wb

            def step_s(carry, s):
                h, out = carry                # h: (V, n, d) chunk outputs
                recv = h
                outs = []
                for v in range(V):
                    sig = v * world + r       # my chunk v's stage index
                    mb = s - sig
                    live = (mb >= 0) & (mb < M)
                    if v == 0:
                        inj = lax.dynamic_index_in_dim(
                            x, jnp.clip(s, 0, M - 1), 0, keepdims=False)
                        inp = jnp.where(r == 0, inj, recv[v])
                    else:
                        inp = jnp.where(r == 0, recv[v - 1], recv[v])
                    yv = lax.cond(
                        live,
                        lambda hh, v=v: _stage(w[v], bb[v], hh),
                        lambda hh: jnp.zeros_like(hh), inp)
                    outs.append(yv)
                hs = jnp.stack(outs)
                # bank the final stage's live output
                last_mb = s - (N - 1)
                live_l = (last_mb >= 0) & (last_mb < M) & (r == world - 1)
                banked = lax.dynamic_update_index_in_dim(
                    out, outs[V - 1], jnp.clip(last_mb, 0, M - 1), 0)
                out = jnp.where(live_l, banked, out)
                hs = lax.ppermute(hs, AXIS, perm)
                return (hs, out), None

            h0 = jnp.zeros((V, n, d), x.dtype)
            out0 = jnp.zeros((M, n, d), x.dtype)
            (_, out), _ = lax.scan(step_s, (h0, out0), jnp.arange(steps))
            diff = (out - y).astype(jnp.float32)
            local = jnp.mean(diff * diff, axis=(1, 2))   # (M,)
            local = jnp.where(r == world - 1, local, jnp.zeros_like(local))
            # LOCAL loss only — the psum for reporting happens OUTSIDE
            # value_and_grad (a psum inside the differentiated function
            # would double-count: its shard_map transpose is psum, so
            # every rank's cotangent would arrive scaled by world)
            return jnp.sum(local) / M

        loss, (gw, gb) = jax.value_and_grad(loss_fn)((w, bb))
        loss = lax.psum(loss, AXIS)
        w_new = w - lr * gw.astype(w.dtype)
        b_new = bb - lr * gb.astype(bb.dtype)
        return w_new[None], b_new[None], loss

    from jax.sharding import PartitionSpec as P
    specs = PPStageParams(w=P(AXIS, None, None, None),
                          b=P(AXIS, None, None))
    prog = _smap(comm, body, 3,
                 in_specs=(specs, P(AXIS, None, None, None),
                           P(AXIS, None, None, None)),
                 out_specs=(P(AXIS, None, None, None),
                            P(AXIS, None, None), P()))

    def step(params: PPStageParams, x, y):
        w, b, loss = prog(params, x, y)
        return PPStageParams(w, b), loss

    step.schedule, step.decision_source = "gpipe", "register"
    step.table, step.stash_slots = None, M
    return step


# ===========================================================================
# the composed (pp, dp, tp) transformer train step
# ===========================================================================


def make_pp_mesh(devices, pp: int, dp: int = 1, tp: int = 1):
    """A (pp, dp, tp) mesh over ``pp*dp*tp`` devices — size-1 axes are
    kept (the specs below name all three)."""
    from jax.sharding import Mesh
    devs = np.array(list(devices)[: pp * dp * tp]).reshape(pp, dp, tp)
    from .mlp import DP_AXIS, TP_AXIS
    return Mesh(devs, (PP_AXIS, DP_AXIS, TP_AXIS))


class PPTransformerParams(NamedTuple):
    """One transformer block per pipeline stage, ZeRO-sharded over dp in
    the travel layout (the ``models/zero.py`` per-layer shapes with a
    leading pp dim):

    * ``attn``: (pp, tp, n_attn_pad) — flat attention bucket per tp
      rank, dp-sharded along the flat dim (spec ``P(pp, tp, dp)``);
    * ``w1t``:  (pp, d_hidden, d_model) — W1-transposed travel layout,
      rows split tp-major then dp (``P(pp, (tp, dp), None)``);
    * ``w2t``:  (pp, d_model, d_hidden) — rows dp, cols tp
      (``P(pp, dp, tp)``).
    """

    attn: jax.Array
    w1t: jax.Array
    w2t: jax.Array


def pp_transformer_specs():
    from jax.sharding import PartitionSpec as P
    from .mlp import DP_AXIS, TP_AXIS
    return PPTransformerParams(
        attn=P(PP_AXIS, TP_AXIS, DP_AXIS),
        w1t=P(PP_AXIS, (TP_AXIS, DP_AXIS), None),
        w2t=P(PP_AXIS, DP_AXIS, TP_AXIS),
    )


def init_pp_transformer(key, mesh, d_model: int, d_hidden: int,
                        n_heads: int) -> PPTransformerParams:
    """Initialize one transformer block per pipeline stage and shard it
    over the (pp, dp, tp) mesh — stage weights 1/dp per dp rank in the
    travel layout (``models/zero.py``'s per-layer shapes)."""
    from jax.sharding import NamedSharding
    from . import zero
    from .mlp import DP_AXIS, TP_AXIS

    pp = mesh.shape[PP_AXIS]
    dp, tp = mesh.shape[DP_AXIS], mesh.shape[TP_AXIS]
    zero._validate_geometry(dp, tp, d_model, d_hidden, n_heads)
    dtp, n_attn = zero._attn_sizes(d_model, tp)
    n_attn_pad = n_attn + (-n_attn) % dp
    s_attn = d_model ** -0.5
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_hidden) ** 0.5
    attn, w1t, w2t = [], [], []
    for lk in jax.random.split(key, pp):
        kq, kk, kv, ko, k1, k2 = jax.random.split(lk, 6)
        wq, wk, wv = (np.asarray(jax.random.normal(
            kx, (d_model, d_model), jnp.float32)) * s_attn
            for kx in (kq, kk, kv))
        wo = np.asarray(jax.random.normal(
            ko, (d_model, d_model), jnp.float32)) * s_attn
        rows = []
        for s in range(tp):
            cols = slice(s * dtp, (s + 1) * dtp)
            wqkv_s = np.concatenate(
                [wq[:, cols], wk[:, cols], wv[:, cols]], axis=1)
            rows.append(np.concatenate(
                [wqkv_s.ravel(), wo[cols, :].ravel(),
                 np.zeros(n_attn_pad - n_attn, np.float32)]))
        attn.append(np.stack(rows))
        w1 = np.asarray(jax.random.normal(
            k1, (d_model, d_hidden), jnp.float32)) * s1
        w2 = np.asarray(jax.random.normal(
            k2, (d_hidden, d_model), jnp.float32)) * s2
        w1t.append(np.ascontiguousarray(w1.T))
        w2t.append(np.ascontiguousarray(w2.T))
    specs = pp_transformer_specs()
    put = lambda a, s: jax.device_put(
        np.stack(a), NamedSharding(mesh, s))
    return PPTransformerParams(attn=put(attn, specs.attn),
                               w1t=put(w1t, specs.w1t),
                               w2t=put(w2t, specs.w2t))


def pp_transformer_engage_reason(d_model: int, d_hidden: int,
                                 batch_per_dp: int, pp: int, dp: int,
                                 tp: int,
                                 overlap: Optional[bool] = None,
                                 bidirectional: bool = True,
                                 wire_dtype=None) -> Optional[str]:
    """None when the composed fused datapath would actually run: the
    relay plan engages for the (batch, d_model) payload AND (dp > 1)
    every per-stage fused leg resolves
    (:func:`~accl_tpu.models.zero.fsdp_engage_reason` — the agmm/mmrs
    MLP plus the fused wgrads; at dp == 1 the ZeRO legs are degenerate
    and the stage block's gathers are identities, so only the relay
    gates).  Otherwise the first decline reason (the
    ``accl_cmatmul_fallback_total`` vocabulary)."""
    from ..ops import pipeline_relay as _relay

    reason = _relay.relay_engage_reason(batch_per_dp, d_model,
                                        jnp.float32, pp, overlap)
    if reason is not None:
        return reason
    if dp > 1:
        from . import zero
        return zero.fsdp_engage_reason(d_model, d_hidden, batch_per_dp,
                                       dp, tp, overlap, bidirectional,
                                       wire_dtype)
    return None


def build_pp_transformer_train_step(mesh, d_model: int, d_hidden: int,
                                    n_heads: int, n_micro: int,
                                    lr: float = 1e-2, *,
                                    schedule: Optional[str] = None,
                                    overlap: Optional[bool] = None,
                                    wire_dtype=None,
                                    bidirectional: bool = True) -> Callable:
    """``step(params, x, y) -> (params, loss)`` — ONE jitted train step
    over the (pp, dp, tp) mesh: a transformer block per pipeline stage
    (flash attention + the agmm/mmrs MLP with ZeRO travel-layout shards
    over dp, Megatron heads/hidden over tp), scheduled 1F1B along pp
    with the per-tick Pallas relay.

    ``x``/``y``: (M, B, d_model) global — microbatches leading, rows
    sharded over dp, replicated over pp/tp (stage 0 injects, the last
    stage holds targets).  SGD update; loss = mean over microbatches of
    the per-microbatch global MSE.

    Resolution (the commit-honesty contract):

    * ``schedule`` as on :func:`build_pp_train_step` ("auto" arbitrates
      relay-vs-tp link occupancy through the cost model);
    * the FUSED datapath runs only when
      :func:`pp_transformer_engage_reason` resolves None at the traced
      batch shape.  A DECLINE (anything but an explicit/session
      ``overlap=False``) falls back WHOLE to the GPipe baseline with
      the flat per-stage datapath — never a degraded unfused rendition
      of the 1F1B program — counted under
      ``accl_cmatmul_fallback_total{op="pp_pipeline"}``.  An explicit
      ``overlap=False`` is a requested baseline: the resolved schedule
      still runs, with the flat datapath, uncounted.

    Backward is stash-input + recompute: each backward tick re-runs the
    stage block under ``jax.vjp`` from the stashed (b, d) input, so the
    live activation set stays O(world) while the fused kernels' custom
    VJPs (mmrs gradient reduce-scatter, fused wgrad) carry the dp legs.

    The returned step carries ``.schedule``, ``.decision_source``,
    ``.fused``, ``.engage_reason``, ``.table``, ``.stash_slots``."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from ..ops import collective_matmul as cm
    from ..ops import pipeline_relay as _relay
    from . import zero
    from .mlp import DP_AXIS, TP_AXIS

    pp = mesh.shape[PP_AXIS]
    dp, tp = mesh.shape[DP_AXIS], mesh.shape[TP_AXIS]
    zero._validate_geometry(dp, tp, d_model, d_hidden, n_heads)
    axes = tuple(mesh.axis_names)
    M = n_micro

    def _resolved_overlap():
        if overlap is None:
            return None if _relay.get_overlap_enabled() else False
        return overlap

    def build(batch_per_dp: int):
        ov = _resolved_overlap()
        payload = 4 * batch_per_dp * d_model
        tp_bytes = 4 * batch_per_dp * d_model
        decision, source = resolve_pp_schedule(
            schedule, pp, M, payload_bytes=payload, tp=tp,
            tp_bytes=tp_bytes)
        reason = pp_transformer_engage_reason(
            d_model, d_hidden, batch_per_dp, pp, dp, tp, ov,
            bidirectional, wire_dtype)
        fused = reason is None
        if not fused and reason != "off":
            # commit honesty: a declining per-stage plan demotes the
            # WHOLE step to the GPipe baseline, counted
            cm._note_fallback(PP_STEP_OP, reason)
            decision, source = "gpipe", "fallback"
        if decision == "1f1b":
            validate_pp_geometry(pp, M, 1)
            tab = schedule_table(pp, M, 1)
        else:
            tab = None
        return decision, source, fused, reason, tab

    wdt = cm._resolve_wire(wire_dtype, np.float32)
    dtp, n_attn = zero._attn_sizes(d_model, tp)
    n_attn_pad = n_attn + (-n_attn) % dp
    h_tp = d_hidden // tp

    def stage_fn_fused(sp, h, ov):
        """One fused transformer block: bucket-gathered attention (its
        gradient rides the wire-staged reduce-scatter) + the agmm MLP
        over dp in travel layout (zero's exact per-layer body)."""
        bucket = zero._bucket_gather(sp.attn, DP_AXIS, wire_dtype) \
            if dp > 1 else sp.attn
        h = zero._attn_sublayer(h, bucket, d_model, tp, n_heads)

        def agmm(trav, panel):
            return cm.all_gather_matmul(trav, panel, DP_AXIS, axes, ov,
                                        bidirectional, wire_dtype)

        if dp > 1:
            mm1 = lambda xt: agmm(sp.w1t, xt)
            mm2 = lambda u: agmm(sp.w2t, u)
        else:
            mm1 = lambda xt: jnp.dot(
                sp.w1t, xt, preferred_element_type=jnp.float32)
            mm2 = lambda u: jnp.dot(
                sp.w2t, u, preferred_element_type=jnp.float32)
        return zero._mlp_sublayer(h, mm1, mm2, tp)

    def stage_fn_flat(sp, h):
        """The baseline block: monolithic dp gathers (identity at
        dp == 1; gradients reduce-scatter through the bucket-gather
        VJP), plain dots, tp psum — zero's flat datapath per stage."""
        if dp > 1:
            bucket = zero._bucket_gather(sp.attn, DP_AXIS, "off")
            w1 = zero._bucket_gather(sp.w1t.reshape(-1), DP_AXIS, "off") \
                .reshape(h_tp, d_model)
            w2 = zero._bucket_gather(sp.w2t.reshape(-1), DP_AXIS, "off") \
                .reshape(d_model, h_tp)
        else:
            bucket, w1, w2 = sp.attn, sp.w1t, sp.w2t
        h = zero._attn_sublayer(h, bucket, d_model, tp, n_heads)
        return zero._mlp_sublayer(
            h,
            lambda xt: jnp.dot(w1, xt, preferred_element_type=jnp.float32),
            lambda u: jnp.dot(w2, u, preferred_element_type=jnp.float32),
            tp)

    def make_local(decision, fused, tab, ov):
        def local_step(p: PPTransformerParams, x, y):
            # local leaves: attn (1, 1, n_attn_pad/dp) etc. — drop the
            # leading pp dim, keep the per-device shard
            sp = PPTransformerParams(
                attn=p.attn[0, 0], w1t=p.w1t[0], w2t=p.w2t[0])
            b = x.shape[1]                   # (M, b, d) local rows

            def stage(spp, h):
                if fused:
                    return stage_fn_fused(spp, h, ov)
                return stage_fn_flat(spp, h)

            if decision == "1f1b":
                new_sp, loss = _pp_1f1b_generic(
                    stage, sp, x, y, tab, pp, M, b, d_model, dp, lr,
                    axes, ov)
            else:
                new_sp, loss = _pp_gpipe_generic(
                    stage, sp, x, y, pp, M, b, d_model, dp, lr)
            new_p = PPTransformerParams(
                attn=new_sp.attn[None, None], w1t=new_sp.w1t[None],
                w2t=new_sp.w2t[None])
            return new_p, loss

        return local_step

    specs = pp_transformer_specs()
    built = {}

    def _get_prog(b: int):
        if b not in built:
            decision, source, fused, reason, tab = build(b)
            local = make_local(decision, fused, tab,
                               _resolved_overlap())
            prog = jax.jit(shard_map(
                local, mesh=mesh,
                in_specs=(specs, P(None, DP_AXIS, None),
                          P(None, DP_AXIS, None)),
                out_specs=(specs, P()),
                check_vma=False))
            built[b] = (prog, decision, source, fused, reason, tab)
            step.schedule, step.decision_source = decision, source
            step.fused, step.engage_reason = fused, reason
            step.table = tab
            step.stash_slots = tab.stash_slots if tab is not None else M
        return built[b][0]

    def step(params: PPTransformerParams, x, y):
        return _get_prog(x.shape[1] // dp)(params, x, y)

    def lower(params, x, y):
        """AOT entry (the *_schedule pin suites): resolve and lower the
        per-batch program for abstract shapes without executing."""
        return _get_prog(x.shape[1] // dp).lower(params, x, y)

    # resolved lazily at the first (traced or lowered) batch shape
    step.schedule = step.decision_source = None
    step.fused = step.engage_reason = None
    step.table = step.stash_slots = None
    step.lower = lower
    return step


def _pp_1f1b_generic(stage, sp, x, y, tab: PPSchedule, pp: int, M: int,
                     b: int, d: int, dp: int, lr: float, axes, ov):
    """The 1F1B masked scan over an arbitrary per-stage block: forward
    ticks run ``stage`` and stash only its (b, d) input; backward ticks
    recompute it under ``jax.vjp`` (the fused kernels' custom VJPs run
    there).  Single-chunk (V = 1) — virtual stages are the simple
    family's; a transformer stage is a whole block."""
    r = lax.axis_index(PP_AXIS)
    T, slots, gslots = tab.steps, tab.stash_slots, tab.grad_slots
    f_mb = jnp.asarray(tab.f_mb)
    f_slot = jnp.asarray(tab.f_slot)
    dy_slot = jnp.asarray(tab.dy_slot)
    b_mb = jnp.asarray(tab.b_mb)
    b_slot = jnp.asarray(tab.b_slot)
    b_in_slot = jnp.asarray(tab.b_in_slot)
    arr_f = jnp.asarray(tab.arr_f_slot)
    arr_b = jnp.asarray(tab.arr_b_slot)

    from ..ops import pipeline_relay as _relay

    upd, at = _slot_update, _slot_read

    zero_g = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, jnp.float32), sp)

    def tick(carry, t):
        acts, inb, f_wire, b_wire, grads, loss_vec = carry
        acts = upd(acts, f_wire, arr_f[t, r])
        inb = upd(inb, b_wire, arr_b[t, r])

        fm, fs, ds = f_mb[t, r], f_slot[t, r], dy_slot[t, r]

        def do_f(ops):
            acts, inb, loss_vec = ops
            mb = jnp.clip(fm, 0, M - 1)
            h_in = jnp.where(
                r == 0,
                lax.dynamic_index_in_dim(x, mb, 0, keepdims=False),
                at(acts, fs))
            acts = upd(acts, h_in, fs)
            h_out = stage(sp, h_in).astype(jnp.float32)
            y_m = lax.dynamic_index_in_dim(y, mb, 0, keepdims=False)
            diff = h_out - y_m
            l = jnp.mean(diff * diff)
            loss_vec = jnp.where(
                ds >= 0,
                lax.dynamic_update_index_in_dim(loss_vec, l, mb, 0),
                loss_vec)
            dy = (2.0 / (b * d * M * dp)) * diff
            inb = upd(inb, dy, ds)
            f_send = jnp.where(ds >= 0, jnp.zeros_like(h_out), h_out)
            return acts, inb, loss_vec, f_send

        acts, inb, loss_vec, f_send = lax.cond(
            fm >= 0, do_f,
            lambda ops: (ops[0], ops[1], ops[2],
                         jnp.zeros((b, d), jnp.float32)),
            (acts, inb, loss_vec))

        bm, bs, bis = b_mb[t, r], b_slot[t, r], b_in_slot[t, r]

        def do_b(ops):
            grads = ops
            h_in = at(acts, bs)
            dy = at(inb, bis)
            _, vjp = jax.vjp(lambda p, h: stage(p, h).astype(jnp.float32),
                             sp, h_in)
            dsp, dh = vjp(dy)
            grads = jax.tree_util.tree_map(
                lambda g, d_: g + d_.astype(jnp.float32), grads, dsp)
            b_send = jnp.where(r == 0, jnp.zeros_like(dh),
                               dh.astype(jnp.float32))
            return grads, b_send

        grads, b_send = lax.cond(
            bm >= 0, do_b,
            lambda ops: (ops, jnp.zeros((b, d), jnp.float32)),
            grads)

        f_wire, b_wire = _relay.pp_relay(f_send, b_send, PP_AXIS, axes, ov)
        return (acts, inb, f_wire, b_wire, grads, loss_vec), None

    acts0 = jnp.zeros((slots, b, d), jnp.float32)    # THE stash: O(world)
    inb0 = jnp.zeros((gslots, b, d), jnp.float32)
    wire0 = jnp.zeros((b, d), jnp.float32)
    carry0 = (acts0, inb0, wire0, wire0, zero_g,
              jnp.zeros((M,), jnp.float32))
    carry, _ = lax.scan(tick, carry0, jnp.arange(T))
    _, _, _, _, grads, loss_vec = carry
    from .mlp import DP_AXIS
    loss = lax.psum(jnp.sum(loss_vec), (PP_AXIS, DP_AXIS)) / M / dp
    new_sp = jax.tree_util.tree_map(
        lambda w, g: w - lr * g.astype(w.dtype), sp, grads)
    return new_sp, loss


def _pp_gpipe_generic(stage, sp, x, y, pp: int, M: int, b: int, d: int,
                      dp: int, lr: float):
    """The GPipe baseline over an arbitrary per-stage block:
    ``jax.value_and_grad`` through the cond-skipped forward scan (all
    residuals stashed by AD — the O(M) memory the 1F1B stash is
    measured against)."""
    r = lax.axis_index(PP_AXIS)
    steps = M + pp - 1
    perm = _fwd_perm(pp)
    from .mlp import DP_AXIS

    def loss_fn(sp):
        def step_s(carry, s):
            h, out = carry
            mb = jnp.clip(s, 0, M - 1)
            inj = lax.dynamic_index_in_dim(x, mb, 0, keepdims=False)
            inp = jnp.where(r == 0, inj, h)
            my_mb = s - r
            live = (my_mb >= 0) & (my_mb < M)
            yv = lax.cond(live,
                          lambda hh: stage(sp, hh).astype(jnp.float32),
                          lambda hh: jnp.zeros_like(hh), inp)
            banked = lax.dynamic_update_index_in_dim(
                out, yv, jnp.clip(my_mb, 0, M - 1), 0)
            out = jnp.where(live & (r == pp - 1), banked, out)
            h = lax.ppermute(yv, PP_AXIS, perm)
            return (h, out), None

        h0 = jnp.zeros((b, d), jnp.float32)
        out0 = jnp.zeros((M, b, d), jnp.float32)
        (_, out), _ = lax.scan(step_s, (h0, out0), jnp.arange(steps))
        diff = out - y
        local = jnp.mean(diff * diff, axis=(1, 2))
        local = jnp.where(r == pp - 1, local, jnp.zeros_like(local))
        # LOCAL loss only (the gpipe-oracle transpose rule above): the
        # dp gradient sum rides the bucket-gather VJP's psum_scatter,
        # and the reporting psum happens outside value_and_grad
        return jnp.sum(local) / M / dp

    loss, grads = jax.value_and_grad(loss_fn)(sp)
    loss = lax.psum(loss, (PP_AXIS, DP_AXIS))
    new_sp = jax.tree_util.tree_map(
        lambda w, g: w - lr * g.astype(w.dtype), sp, grads)
    return new_sp, loss


# ---------------------------------------------------------------------------
# plan inspection CLI (the synth --explain pattern; ci_gate points here)
# ---------------------------------------------------------------------------


def _explain(world: int, n_micro: int, interleave: int = 1) -> str:
    lines = [f"pipeline schedule for world={world} n_micro={n_micro} "
             f"interleave={interleave}:"]
    try:
        tab = schedule_table(world, n_micro, interleave)
        lines += [
            f"  1f1b:  {tab.steps} ticks, stash={tab.stash_slots} "
            f"slots (max live {tab.max_live}), "
            f"bubble={tab.bubble_fraction:.3f}",
        ]
    except ValueError as e:
        lines += [f"  1f1b:  DEGENERATE — {e}"]
    gp = gpipe_bubble_fraction(world, n_micro, interleave)
    N = world * interleave
    lines += [f"  gpipe: {2 * (n_micro + N - 1)} ticks, stash="
              f"{n_micro} microbatches, bubble={gp:.3f}"]
    decision, source = resolve_pp_schedule(
        None, world, n_micro, payload_bytes=1 << 20,
        interleave=interleave)
    lines += [f"  resolve_pp_schedule(): {decision} (source={source})"]
    return "\n".join(lines)


def _main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Inspect pipeline-schedule decisions without a live "
                    "session (the synth --explain pattern)")
    ap.add_argument("--explain", nargs="+", type=int, metavar="N",
                    help="world n_micro [interleave]")
    args = ap.parse_args(argv)
    if not args.explain or len(args.explain) < 2:
        ap.print_help()
        return 2
    print(_explain(*args.explain[:3]))
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
