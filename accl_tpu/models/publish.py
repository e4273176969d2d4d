"""Live weight publication — the fused train→serve re-shard collective.

The repo holds both halves of the RLHF/online-learning shape: a ZeRO-3
trainer whose weights live permanently sharded in **travel layout**
(``models/zero.py`` — per-layer Wqkvᵀ/Woᵀ blocks split over the (dp, tp)
mesh) and a tp-sharded decode step (``models/decode.py``, fleet layer in
``models/serving.py``).  This module is the bridge: a versioned,
epoch-stamped **weight publication collective** that re-shards the
trainer's dp-partitioned travel shards into the decode replicas' tp
layout as ONE fused jitted program — no host gather, no materialized
full weight on any rank.

**The re-shard route** is the exact inverse of the travel construction
(:func:`zero.init_zero_fsdp`): within each tp rank, a layer's travel
blocks are 1/dp row shards, so the fused program is a per-bucket
**AG×slice composition** — one dp all-gather per travel bucket (Wqkvᵀ
and Woᵀ), then pad-row slice + transpose into the decode layout, with
outputs landing directly under :func:`decode.param_specs` (wq/wk/wv
columns over tp, wo rows over tp).  The gather leg:

* resolves through :func:`synth.resolve_publish_route` so the cost
  model prices the route per transport tier (a two-tier plan's
  cross-slice leg at effective :func:`synth.dcn_wire_bytes`), the
  ``plan_source``/``plan_shape`` honesty pair riding the ticket;
* stages in ``dcn_wire_dtype`` via the cmatmul wire codecs
  (:func:`cm._wire_cast` — "off" is bit-exact and pinned by the tests,
  ``bf16_sr`` rides the stochastic-rounding lane);
* applies the round-20 **n-block discipline** for shards that would
  bust the staging budget: the gather splits into row blocks inside
  the SAME program (:func:`publish_nblock`), and with blocking
  disabled such shards decline honestly (``vmem_miss``).

**Honesty**: the committed fallback is the host-gather baseline
(:func:`host_gather_publish` — ``np.asarray`` of every travel bucket,
the exact round-trip this module exists to delete), counted once per
publisher build under ``accl_cmatmul_fallback_total{op="publish"}``
with the cmatmul reason vocabulary ("off" is a requested baseline,
never counted).

**Versioning / fault domains**: every publication is stamped with the
trainer session epoch at launch.  A publication that observes an epoch
bump or a new death verdict between re-shard and landing — or an
injected ``publish.commit`` fault — commits NOTHING: the serving tier
keeps decoding version N, the stale attempt is counted
(``accl_publish_total{outcome="stale"}``) and the next call republishes
on whatever mesh :meth:`WeightPublisher.rebind` was given after the
shrink.  There is no interleaving in which a replica observes a torn
swap: landing stages into the replica's shadow slot
(:meth:`serving.DecodeReplica.stage_weights`) and the pointer swap
happens between decode ticks (:meth:`swap_weights`), never inside one.

See ``docs/serving.md`` §Weight publication for the dataflow diagram,
the version state machine and the fault-domain contract.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map
from ..obs import flight as _flight
from ..obs import metrics
from .. import fault
from . import decode
from . import zero
from .mlp import DP_AXIS, TP_AXIS

__all__ = [
    "PUBLISH_OP", "PublishTicket", "WeightPublisher",
    "build_publish_program", "host_gather_publish",
    "publish_engage_reason", "publish_engages", "publish_nblock",
    "set_fused_enabled", "get_fused_enabled", "publication_bytes",
]

#: the fallback-counter op label (``accl_cmatmul_fallback_total{op=...}``)
PUBLISH_OP = "publish"

#: staging budget per gathered travel bucket (bytes) — past it the
#: gather must n-block (blocking on) or decline ``vmem_miss`` (blocking
#: off, the pre-round-20 behavior).  Sized like the cmatmul scoped-VMEM
#: arm: a gathered bucket is resident while it transposes.
_STAGE_BUDGET = 4 << 20

#: session A/B register (``ACCLConfig.publish_fused`` write-through):
#: False pins the host-gather baseline for every publisher that does
#: not override per-call — a REQUESTED baseline, never counted.
_FUSED_DEFAULT = True


def set_fused_enabled(enabled: bool) -> None:
    """Config write-through (the ``zero.set_overlap_enabled`` pattern):
    the session-level fused-vs-host-gather A/B switch, seeded by
    ``bench.autotune_publish`` on the live mesh."""
    global _FUSED_DEFAULT
    _FUSED_DEFAULT = bool(enabled)


def get_fused_enabled() -> bool:
    return _FUSED_DEFAULT


# ---------------------------------------------------------------------------
# engage policy (the cmatmul honesty discipline)
# ---------------------------------------------------------------------------


def publish_nblock(gathered_bytes: int, local_rows: int) -> Optional[int]:
    """Row-block count for one travel bucket's gather leg: 1 when the
    gathered bucket fits :data:`_STAGE_BUDGET`, else the smallest
    divisor of ``local_rows`` that brings each block under budget
    (round-20 discipline: blocks are disjoint row slices whose payloads
    sum to the unsplit payload — wire-neutral).  None when blocking is
    disabled and the bucket busts the budget (the caller declines
    ``vmem_miss``) or when no divisor fits."""
    from ..ops import collective_matmul as cm

    if gathered_bytes <= _STAGE_BUDGET:
        return 1
    if not cm.get_nblock_enabled():
        return None
    need = -(-gathered_bytes // _STAGE_BUDGET)
    for nb in range(int(need), local_rows + 1):
        if local_rows % nb == 0 and gathered_bytes // nb <= _STAGE_BUDGET:
            return nb
    return None


def publish_engage_reason(d_model: int, n_heads: int, dp: int, tp: int,
                          fused: Optional[bool] = None) -> Optional[str]:
    """None when the fused re-shard program would actually run for this
    geometry; otherwise the first decline reason in the
    ``accl_cmatmul_fallback_total`` vocabulary (``"off"`` — the session
    register or per-call ``fused=False`` requested the host-gather
    baseline, never counted; ``"geometry"`` — the travel/decode layouts
    don't divide; ``"vmem_miss"`` — a gathered bucket busts the staging
    budget and n-blocking is disabled)."""
    if fused is None:
        fused = get_fused_enabled()
    if not fused:
        return "off"
    if (d_model % n_heads or n_heads % tp or d_model % tp
            or d_model % dp):
        return "geometry"
    _, _, q_rows_pad = zero._attn_travel_sizes(d_model, tp, dp)
    if q_rows_pad % dp or (d_model // dp) == 0:
        return "geometry"
    for gathered, rows in ((q_rows_pad * d_model * 4, q_rows_pad // dp),
                           (d_model * (d_model // tp) * 4, d_model // dp)):
        if publish_nblock(gathered, rows) is None:
            return "vmem_miss"
    return None


def publish_engages(d_model: int, n_heads: int, dp: int, tp: int,
                    fused: Optional[bool] = None) -> bool:
    """:func:`publish_engage_reason` collapsed to a bool (the bench
    lane's ``fused_engaged`` honesty flag)."""
    return publish_engage_reason(d_model, n_heads, dp, tp, fused) is None


def publication_bytes(n_layers: int, d_model: int) -> int:
    """Decode-layout payload of one publication: per layer, wq/wk/wv/wo
    at (d, d) f32 each — what ``accl_publish_bytes_total`` counts and
    the bench lane's wire ratio is taken against."""
    return n_layers * 4 * d_model * d_model * 4


# ---------------------------------------------------------------------------
# the fused program: ONE jitted shard_map over the trainer (dp, tp) mesh
# ---------------------------------------------------------------------------


def _staged_gather(x, wdt, sr: bool, nb: int):
    """Wire-staged dp all-gather of one travel bucket shard, optionally
    row-blocked: each block casts to the wire dtype, gathers over dp,
    and restores the operand dtype; blocks reassemble to the EXACT
    row order of the unblocked gather (per-rank-major), so blocking is
    value-neutral at wire "off" bit-for-bit."""
    from ..ops import collective_matmul as cm

    if nb <= 1:
        xw = cm._wire_cast(x, wdt, stochastic=sr)
        return lax.all_gather(xw, DP_AXIS, axis=0,
                              tiled=True).astype(x.dtype)
    rows = x.shape[0]
    chunk = rows // nb
    parts = []
    for j in range(nb):
        xw = cm._wire_cast(x[j * chunk:(j + 1) * chunk], wdt,
                           stochastic=sr)
        g = lax.all_gather(xw, DP_AXIS, axis=0, tiled=False)
        parts.append(g.astype(x.dtype))       # (dp, chunk, d) each
    return jnp.concatenate(parts, axis=1).reshape(-1, x.shape[1])


def build_publish_program(mesh, n_layers: int, d_model: int,
                          n_heads: int, wire_dtype=None):
    """Build the fused publication program: ``fn(FSDPParams) ->
    tuple[DecodeParams, ...]`` (one per trainer layer), ONE jitted
    shard_map over the trainer's (dp, tp) mesh.

    Per layer and per tp rank s the program all-gathers the dp row
    shards of the Wqkvᵀ travel block (rows ``[0:3·dtp]`` after the pad
    slice are exactly ``[wq‖wk‖wv][:, s·dtp:(s+1)·dtp]ᵀ``) and of the
    Woᵀ block (columns ``s·dtp:(s+1)·dtp``), then transposes in place —
    the outputs are BORN in the decode layout
    (:func:`decode.param_specs`: q/k/v columns over tp, o rows over
    tp; dp holds replicas).  The only collectives in the traced program
    are the planned dp gathers — no all_to_all, no psum, no host
    transfer (pinned by tests/test_publish.py)."""
    from ..ops import collective_matmul as cm

    dp, tp = mesh.shape[DP_AXIS], mesh.shape[TP_AXIS]
    zero._validate_geometry(dp, tp, d_model, d_model, n_heads)
    dtp, q_rows, q_rows_pad = zero._attn_travel_sizes(d_model, tp, dp)
    wdt, sr = cm._resolve_wire_codec(
        "off" if wire_dtype is None else wire_dtype, jnp.float32)
    nb_q = publish_nblock(q_rows_pad * d_model * 4, q_rows_pad // dp)
    nb_o = publish_nblock(d_model * dtp * 4, d_model // dp)
    if nb_q is None or nb_o is None:
        raise ValueError(
            "publication bucket busts the staging budget with n-blocking "
            "disabled — the caller must decline to the host-gather "
            "baseline (publish_engage_reason() == 'vmem_miss')")

    def body(wqkvt, wot):
        outs: List[decode.DecodeParams] = []
        for bq, bo in zip(wqkvt, wot):
            g = _staged_gather(bq, wdt, sr, nb_q)[:q_rows]
            go = _staged_gather(bo, wdt, sr, nb_o)
            outs.append(decode.DecodeParams(
                wq=g[0:dtp].T, wk=g[dtp:2 * dtp].T,
                wv=g[2 * dtp:3 * dtp].T, wo=go.T))
        return tuple(outs)

    per = lambda s: tuple(s for _ in range(n_layers))
    out_specs = per(decode.DecodeParams(
        wq=P(None, TP_AXIS), wk=P(None, TP_AXIS),
        wv=P(None, TP_AXIS), wo=P(TP_AXIS, None)))
    prog = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(per(P((TP_AXIS, DP_AXIS), None)), per(P(DP_AXIS, TP_AXIS))),
        out_specs=out_specs,
        check_vma=False))
    return lambda p: prog(p.wqkvt, p.wot)


def host_gather_publish(params: zero.FSDPParams, d_model: int, tp: int,
                        dp: int) -> Tuple[decode.DecodeParams, ...]:
    """The COUNTED baseline the fused program is benched against: gather
    every travel bucket to the host (``np.asarray`` — the full weight
    materializes in controller memory, the round-trip the collective
    deletes) and invert the travel construction there
    (:func:`zero.attn_from_travel` — the one shared copy of the
    inversion math, so baseline and fused path can never drift)."""
    outs = []
    for wqkvt, wot in zip(params.wqkvt, params.wot):
        wq, wk, wv, wo = zero.attn_from_travel(
            np.asarray(wqkvt), np.asarray(wot), d_model, tp, dp)
        outs.append(decode.DecodeParams(
            wq=jnp.asarray(wq), wk=jnp.asarray(wk),
            wv=jnp.asarray(wv), wo=jnp.asarray(wo)))
    return tuple(outs)


# ---------------------------------------------------------------------------
# the publisher: version/epoch stamping, landing, fault-domain guard
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PublishTicket:
    """One publication attempt's receipt — the honesty record the bench
    lane and the chaos drill read.  ``outcome`` is the
    ``accl_publish_total`` label: "committed" (version landed on every
    replica's shadow slot), "stale" (epoch bump / death verdict /
    injected fault between re-shard and landing — NOTHING landed)."""

    version: int
    epoch: int
    step: int
    outcome: str                  # committed | stale
    route: str                    # fused | host_gather
    fused: bool
    reason: Optional[str]         # engage decline reason (None = engaged)
    wire_dtype: str
    nbytes: int
    wire_bytes: int
    plan_source: Optional[str]
    plan_shape: Optional[str]
    n_layers: int
    dp: int
    tp: int


class WeightPublisher:
    """Trainer-side publication endpoint over one (dp, tp) mesh.

    Construction resolves the route ONCE (engage policy → fused program
    or counted host-gather baseline; ``synth.resolve_publish_route`` →
    the priced plan whose source/shape ride every ticket) and
    :meth:`publish` stamps each attempt with the session epoch.  After
    a trainer shrink, :meth:`rebind` re-resolves everything on the
    surviving mesh while the version counter carries over — the serving
    tier never observes a version number reused."""

    def __init__(self, acc, mesh, n_layers: int, d_model: int,
                 d_hidden: int, n_heads: int, wire_dtype=None,
                 fused: Optional[bool] = None):
        self.acc = acc
        self.n_layers, self.d_model = int(n_layers), int(d_model)
        self.d_hidden, self.n_heads = int(d_hidden), int(n_heads)
        self.version = 0
        self._fused_req = fused
        self._wire_req = wire_dtype
        self.rebind(mesh)

    # -- route resolution --------------------------------------------------

    def rebind(self, mesh, wire_dtype=None) -> None:
        """(Re-)resolve the publication route on ``mesh`` — bring-up and
        the post-``recover()`` shrink path share this: engage policy,
        fallback accounting (once per build, the trace-time cmatmul
        discipline), the synth plan and the fused program cache all
        re-derive; :attr:`version` is preserved."""
        from ..ops import collective_matmul as cm
        from ..parallel import synth

        self.mesh = mesh
        self.dp = int(mesh.shape[DP_AXIS])
        self.tp = int(mesh.shape[TP_AXIS])
        zero._validate_geometry(self.dp, self.tp, self.d_model,
                                self.d_hidden, self.n_heads)
        if wire_dtype is not None:
            self._wire_req = wire_dtype
        cfg = self.acc.config if self.acc is not None else None
        wire = self._wire_req
        if wire is None:
            wire = cfg.dcn_wire_dtype if cfg is not None else "off"
        self.wire_dtype = wire
        self.reason = publish_engage_reason(
            self.d_model, self.n_heads, self.dp, self.tp,
            fused=self._fused_req)
        self.fused = self.reason is None
        if self.reason is not None and self.reason != "off":
            cm._note_fallback(PUBLISH_OP, self.reason)
        self.nbytes = publication_bytes(self.n_layers, self.d_model)
        self.plan = None
        if self.acc is not None:
            # price the per-bucket gather leg: the dp-shard payload of
            # the largest travel bucket (the allgather byte convention
            # — per-block bytes)
            _, _, qrp = zero._attn_travel_sizes(self.d_model, self.tp,
                                                self.dp)
            blk = (qrp // self.dp) * self.d_model
            self.plan = synth.resolve_publish_route(
                self.acc.global_comm(), cfg, blk * 4, count=blk)
        self.wire_bytes = synth.dcn_wire_bytes(
            self.nbytes, wire if wire != "off" else None,
            count=self.nbytes // 4)
        self._program = None

    def _ensure_program(self):
        if self._program is None:
            self._program = build_publish_program(
                self.mesh, self.n_layers, self.d_model, self.n_heads,
                wire_dtype=self.wire_dtype)
        return self._program

    # -- epoch/death observation (the fault-domain guard) ------------------

    def _epoch_view(self) -> Tuple[int, int]:
        acc = self.acc
        epoch = int(getattr(acc, "_epoch", 0) or 0) if acc else 0
        fabric = getattr(acc, "_fabric", None) if acc else None
        dead = len(getattr(fabric, "dead_peers", ()) or ()) if fabric \
            else 0
        return epoch, dead

    # -- publication -------------------------------------------------------

    def reshard(self, state: zero.ZeroFSDPState
                ) -> Tuple[decode.DecodeParams, ...]:
        """Run the re-shard only (no landing, no version bump) — the
        bench lane's timed unit and the parity tests' subject."""
        if self.fused:
            return self._ensure_program()(state.p)
        return host_gather_publish(state.p, self.d_model, self.tp,
                                   self.dp)

    def publish(self, state: zero.ZeroFSDPState,
                replicas: Sequence = (), layer: int = 0,
                step: Optional[int] = None) -> PublishTicket:
        """One publication: re-shard ``state``'s travel shards, verify
        the epoch/death view did not move underneath the re-shard, then
        land version N+1 into every replica's SHADOW slot
        (:meth:`serving.DecodeReplica.stage_weights` — version N keeps
        decoding until each replica's between-tick
        :meth:`swap_weights`).  A stale observation (or an injected
        ``publish.commit`` fault) lands NOTHING and counts
        ``accl_publish_total{outcome="stale"}`` — the no-torn-swap
        contract.  Timed into
        ``accl_latency_dispatch_seconds{path="publish"}``."""
        from ..parallel import synth
        from ..constants import operation

        t0 = metrics.tick()
        epoch0, dead0 = self._epoch_view()
        t = int(state.t) if step is None else int(step)
        params = self.reshard(state)
        jax.block_until_ready(params)
        stale_reason = None
        try:
            if fault.ENABLED:
                fault.point("publish.commit")
        except fault.FaultInjected as e:
            stale_reason = f"injected:{e.kind}"
        epoch1, dead1 = self._epoch_view()
        if stale_reason is None and (epoch1 != epoch0 or dead1 != dead0):
            stale_reason = "epoch_moved" if epoch1 != epoch0 \
                else "peer_failed"
        if stale_reason is not None:
            metrics.inc("accl_publish_total",
                        labels=(("outcome", "stale"),))
            _flight.record("publish", outcome="stale",
                           version=self.version + 1, epoch=epoch0,
                           step=t, reason=stale_reason)
            return self._ticket(self.version + 1, epoch0, t, "stale")
        version = self.version + 1
        for r in replicas:
            r.stage_weights(params[layer], version)
        self.version = version
        if self.plan is not None and self.acc is not None:
            synth.note_dcn_wire_bytes(operation.allgather, self.plan,
                                      self.nbytes,
                                      count=self.nbytes // 4)
        metrics.inc("accl_publish_total",
                    labels=(("outcome", "committed"),))
        metrics.inc("accl_publish_bytes_total", float(self.nbytes),
                    labels=(("dtype", "float32"),))
        metrics.note_latency_dispatch("publish", t0)
        _flight.record("publish", outcome="committed", version=version,
                       epoch=epoch0, step=t,
                       route="fused" if self.fused else "host_gather",
                       replicas=len(list(replicas)))
        return self._ticket(version, epoch0, t, "committed")

    def _ticket(self, version: int, epoch: int, step: int,
                outcome: str) -> PublishTicket:
        return PublishTicket(
            version=version, epoch=epoch, step=step, outcome=outcome,
            route="fused" if self.fused else "host_gather",
            fused=self.fused, reason=self.reason,
            wire_dtype=self.wire_dtype, nbytes=self.nbytes,
            wire_bytes=self.wire_bytes,
            plan_source=self.plan.source if self.plan else None,
            plan_shape=self.plan.shape if self.plan else None,
            n_layers=self.n_layers, dp=self.dp, tp=self.tp)
