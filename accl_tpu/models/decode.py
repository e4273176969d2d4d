"""Continuous-batching decode-step model — the inference-serving datapath.

Everything the repo built through round 12 is throughput-shaped
(training steps, MiB-scale payloads); a millions-of-users service is
latency-shaped: one token per live sequence per step, a KV cache that
grows every step, sequences arriving and finishing at arbitrary times.
This module is that workload expressed on the framework:

* the **paged KV cache** lives in :func:`accl_tpu.ops.flash.flash_decode`'s
  layout — fixed-size pages per kv head indexed by a per-slot block
  table, so cache growth NEVER changes an array shape (no recompilation
  as sequences lengthen; the jitted step is compiled once and reused for
  the whole serving session);
* **continuous batching** is slot management over that layout:
  :func:`admit` turns a free slot into a fresh sequence and
  :func:`retire` releases it, both by rewriting table rows and lengths —
  O(1) host work, no tensor reshapes, concurrent sequences of unequal
  length decode in ONE kernel launch via per-slot ``seq_lens``;
* the **decode step** (:func:`build_decode_step`) runs under tensor
  parallelism: heads split over tp, the fused Wqkv projection rides
  ``all_gather_matmul`` and the Wo row-parallel combine rides
  ``matmul_reduce_scatter`` where the kernel plans engage (the mlp/zero
  plan-policy discipline — anything less runs the psum baseline, same
  math), the attention itself is :func:`flash.flash_decode` over each
  rank's local heads (embarrassingly parallel: GQA groups never straddle
  ranks), and the new token's K/V land in place via
  :func:`flash.kv_cache_append` — the whole step is ONE jitted
  ``shard_map`` program;
* :func:`publish_tokens` is the serving tier's host-side small-message
  traffic: one decode step's sampled token ids fanned out to the other
  controllers' ranks as token-sized eager sends — the bursty
  sub-threshold workload the round-13 latency tier (eager fast path +
  flat/tree schedules, ``ACCLConfig.latency_tier_threshold``) exists
  for, and the first consumer that actually stresses ``sendrecv.py``'s
  matching engine and ``rxpool.py``'s slot pool with decode-shaped load;
* the **throughput tier** (round 18): :func:`build_prefill_step` admits
  prompts straight into the paged layout one page-granular chunk per
  launch (no host token loop, no monolithic unpaged cache),
  :func:`build_spec_decode_step` pushes S_q = k draft tokens per slot
  through one multi-query page sweep with verify-and-accept in the
  epilogue (accepted prefixes advance ``seq_lens``, rejected tokens'
  page rows roll back bit-exactly; k = 1 IS the plain step), and the
  page pools optionally quantize AT REST (``ACCLConfig.kv_cache_dtype``
  — in-kernel dequant on the read sweep, 2x KV HBM per slot at int8).
  Step dispatch is phase-timed (``accl_latency_dispatch_seconds{path=
  prefill|decode|verify}``) and token throughput counted
  (``accl_serving_tokens_total``).

Invariants (enforced by construction in :func:`init_decode_state`, and
what :func:`flash.kv_cache_append` relies on): block tables name
DISJOINT pool pages across slots, every table entry is a valid pool
index even while retired, and ``seq_lens[b] <= pages_max * page``.

See ``docs/serving.md`` for the dataflow and the latency-tier story.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from .. import device_api as dapi
from ..constants import dataType
from .mlp import TP_AXIS

__all__ = [
    "DecodeParams", "DecodeState", "init_decode_params",
    "init_decode_state", "admit", "retire", "free_slots", "full_slots",
    "build_decode_step", "build_prefill_step", "build_spec_decode_step",
    "decode_step_reference", "spec_step_reference", "decode_engages",
    "decode_engage_reasons", "accept_lengths", "note_serving_tokens",
    "make_decode_mesh", "shard_decode", "publish_tokens",
    "publish_tokens_batch", "pack_token_records", "unpack_token_records",
    "used_pages", "extract_session", "install_session",
    "assert_swappable",
]


class DecodeParams(NamedTuple):
    """One attention block's projections. Global shapes (sharded over tp
    by :func:`param_specs` — q/k/v columns, o rows):

    * ``wq``: (d_model, H·hd)      * ``wk``/``wv``: (d_model, H_kv·hd)
    * ``wo``: (H·hd, d_model)

    ``H % tp == 0`` and ``H_kv % tp == 0`` so each rank owns whole GQA
    groups (g = H/H_kv query heads per kv head stay on one rank — the
    decode kernel's tile never straddles ranks)."""

    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array


class DecodeState(NamedTuple):
    """The serving session's device-resident cache + slot bookkeeping.

    * ``k_pages``/``v_pages``: (H_kv, n_pages, page, hd) page pools
      (tp-sharded over kv heads);
    * ``block_tables``: (slots, pages_max) int32 — slot b's page chain
      (disjoint across slots, always valid pool indices);
    * ``seq_lens``: (slots,) int32 live token counts;
    * ``active``: (slots,) bool — admitted slots. Retired slots keep
      valid table rows (the append kernel must name SOME row) but
      never advance and output zeros.

    Every shape is static in (slots, pages_max, page): admission,
    retirement and growth are VALUE changes only — the jitted decode
    step never recompiles over a sequence's lifetime."""

    k_pages: jax.Array
    v_pages: jax.Array
    block_tables: jax.Array
    seq_lens: jax.Array
    active: jax.Array


def init_decode_params(key, d_model: int, n_heads: int, n_kv_heads: int,
                       head_dim: int, dtype=jnp.float32) -> DecodeParams:
    if n_heads % n_kv_heads:
        raise ValueError(f"n_heads {n_heads} % n_kv_heads {n_kv_heads}")
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = (1.0 / d_model) ** 0.5
    return DecodeParams(
        wq=jax.random.normal(kq, (d_model, n_heads * head_dim), dtype) * s,
        wk=jax.random.normal(kk, (d_model, n_kv_heads * head_dim), dtype) * s,
        wv=jax.random.normal(kv, (d_model, n_kv_heads * head_dim), dtype) * s,
        wo=jax.random.normal(ko, (n_heads * head_dim, d_model), dtype)
        * (1.0 / (n_heads * head_dim)) ** 0.5,
    )


def param_specs() -> DecodeParams:
    return DecodeParams(wq=P(None, TP_AXIS), wk=P(None, TP_AXIS),
                        wv=P(None, TP_AXIS), wo=P(TP_AXIS, None))


def assert_swappable(old: DecodeParams, new: DecodeParams) -> None:
    """The no-recompile invariant of a live weight publication
    (``models/publish.py``): a staged version must match the serving
    version leaf-for-leaf in shape and dtype, so the replica's jitted
    decode step — keyed on abstract values only — survives the pointer
    swap without retracing.  Raises ``ValueError`` naming the first
    mismatched projection; a publication that would force a recompile
    must fail at STAGING time, never between two decode ticks."""
    for name, a, b in zip(DecodeParams._fields, old, new):
        if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
            raise ValueError(
                f"staged weights not swappable: {name} is "
                f"{tuple(b.shape)}/{b.dtype} vs serving "
                f"{tuple(a.shape)}/{a.dtype} — a version swap must "
                f"never retrace the decode step")


def state_specs() -> DecodeState:
    return DecodeState(k_pages=P(TP_AXIS), v_pages=P(TP_AXIS),
                       block_tables=P(), seq_lens=P(), active=P())


def init_decode_state(slots: int, pages_max: int, page: int,
                      n_kv_heads: int, head_dim: int,
                      dtype=jnp.float32,
                      kv_dtype: Optional[str] = None) -> DecodeState:
    """Zeroed pools + the canonical DISJOINT block-table partition: slot
    b owns pool pages ``[b·pages_max, (b+1)·pages_max)``. Slots start
    retired; :func:`admit` brings them live.

    ``kv_dtype`` picks the pools' AT-REST codec (None = the session
    register ``ACCLConfig.kv_cache_dtype``): "off" stores ``dtype``
    (bit-exact writes), "bf16"/"bf16_sr" store bfloat16, "int8" stores
    fixed-scale quantized int8 — halving KV HBM per slot vs bf16. The
    codec is thereafter dtype-driven: every append/prefill write
    quantizes to the pool dtype, every read (kernel sweep or gathered
    reference) dequantizes, so the rest of the serving loop never
    branches on it."""
    from ..ops import flash

    n_pages = slots * pages_max
    store = flash.kv_storage_dtype(dtype, kv_dtype)
    shape = (n_kv_heads, n_pages, page, head_dim)
    return DecodeState(
        k_pages=jnp.zeros(shape, store),
        v_pages=jnp.zeros(shape, store),
        block_tables=jnp.arange(n_pages, dtype=jnp.int32
                                ).reshape(slots, pages_max),
        seq_lens=jnp.zeros((slots,), jnp.int32),
        active=jnp.zeros((slots,), bool),
    )


def admit(state: DecodeState, slot: int) -> DecodeState:
    """Admit a fresh sequence into ``slot``: length resets, the slot
    goes live. O(1) bookkeeping — no pool traffic (stale page content
    is unreachable past ``seq_lens``), no recompilation."""
    return state._replace(
        seq_lens=state.seq_lens.at[slot].set(0),
        active=state.active.at[slot].set(True))


def retire(state: DecodeState, slot: int) -> DecodeState:
    """Release ``slot``: it stops advancing (the append masks it, the
    kernel outputs zeros at length 0) and is free for re-admission. Its
    block-table row stays valid — the append's scatter lane must name
    SOME pool row even for inactive slots."""
    return state._replace(
        seq_lens=state.seq_lens.at[slot].set(0),
        active=state.active.at[slot].set(False))


def free_slots(state: DecodeState) -> list:
    """Host-side admission helper: the slot indices currently retired."""
    return [int(i) for i in np.nonzero(~np.asarray(state.active))[0]]


def full_slots(state: DecodeState) -> list:
    """Host-side eviction signal: active slots whose cache is at
    capacity (``pages_max · page`` tokens). The decode step stops
    appending for them (the capacity guard — growing past the table row
    would corrupt an earlier page), so the serving loop should retire
    or migrate them."""
    page = state.k_pages.shape[2]
    cap = state.block_tables.shape[1] * page
    full = np.asarray(state.active) & (np.asarray(state.seq_lens) >= cap)
    return [int(i) for i in np.nonzero(full)[0]]


# ---------------------------------------------------------------------------
# the decode step
# ---------------------------------------------------------------------------

def make_decode_mesh(devices, tp: int) -> Mesh:
    devs = np.array(list(devices)[:tp])
    return Mesh(devs, (TP_AXIS,))


def shard_decode(params: DecodeParams, state: DecodeState,
                 mesh: Mesh) -> Tuple[DecodeParams, DecodeState]:
    """Place params/state under the tp sharding the step expects."""
    put = lambda tree, specs: jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), tree, specs)
    return put(params, param_specs()), put(state, state_specs())


def decode_engages(slots: int, d_model: int, n_heads: int,
                   n_kv_heads: int, head_dim: int, tp: int,
                   overlap: Optional[bool] = None,
                   bidirectional: bool = True,
                   wire_dtype=None, dtype=jnp.float32) -> bool:
    """True when the tp projections of :func:`build_decode_step` would
    ride the FUSED collective-matmul kernels at these shapes (session
    registers + VMEM plans + rung — the mlp/zero honesty resolution;
    the bench lane's ``fused_engaged`` flag). The attention kernel's
    own paged/unpaged resolution is separate (``flash.decode_plan``)."""
    from ..ops import collective_matmul as cm

    if tp <= 1 or slots % tp or n_heads % tp or n_kv_heads % tp:
        return False
    qkv_cols = (n_heads + 2 * n_kv_heads) // tp * head_dim
    return (cm.agmm_engages(slots // tp, d_model, qkv_cols, tp, dtype,
                            overlap, bidirectional, wire_dtype=wire_dtype)
            and cm.mmrs_engages(slots, n_heads // tp * head_dim, d_model,
                                tp, dtype, overlap, bidirectional,
                                wire_dtype=wire_dtype))


def decode_engage_reasons(slots: int, d_model: int, n_heads: int,
                          n_kv_heads: int, head_dim: int, tp: int,
                          page: Optional[int] = None,
                          pages_max: Optional[int] = None,
                          spec_tokens: int = 1,
                          prefill_chunk: Optional[int] = None,
                          overlap: Optional[bool] = None,
                          bidirectional: bool = True,
                          wire_dtype=None, dtype=jnp.float32,
                          kv_dtype: Optional[str] = None) -> dict:
    """The serving datapath's engage-honesty introspection, one level
    deeper than :func:`decode_engages`' bool: every leg's resolved
    decline reason (None = engages), so the bench lanes and the
    admission loop can say WHICH kernel a session would actually run —
    never a degraded claim.

    Keys: ``qkv``/``wo`` — the tp projections' collective-matmul
    verdicts (``agmm_engage_reason``/``mmrs_engage_reason`` vocabulary:
    off | no_interpret | threshold | vmem_miss | geometry); with
    ``page``/``pages_max`` given, ``attention`` — the single-token
    ``decode_plan`` verdict ("ok" or its decline reason), ``spec`` —
    the same plan at ``span = spec_tokens`` (the multi-token query
    tile), and ``prefill`` — the ``prefill_plan`` verdict at
    ``prefill_chunk`` (None = the plan's own chunk pick); ``kv_quant``
    — the active at-rest codec ("off" = full-width pools).  All
    verdicts resolve the session registers exactly as dispatch would
    (the round-11 reasons-can-never-drift discipline)."""
    from ..ops import collective_matmul as cm
    from ..ops import flash

    reasons = {}
    if tp <= 1 or slots % tp or n_heads % tp or n_kv_heads % tp:
        reasons["qkv"] = reasons["wo"] = "geometry"
    else:
        qkv_cols = (n_heads + 2 * n_kv_heads) // tp * head_dim
        reasons["qkv"] = cm.agmm_engage_reason(
            slots // tp, d_model, qkv_cols, tp, dtype, overlap,
            bidirectional, wire_dtype=wire_dtype)
        reasons["wo"] = cm.mmrs_engage_reason(
            slots, n_heads // tp * head_dim, d_model, tp, dtype,
            overlap, bidirectional, wire_dtype=wire_dtype)
    kv_mode = kv_dtype or flash.get_kv_cache_dtype()
    reasons["kv_quant"] = kv_mode
    if page is not None and pages_max is not None:
        itemsize = jnp.dtype(dtype).itemsize
        kvi = jnp.dtype(flash.kv_storage_dtype(dtype, kv_mode)).itemsize
        # per-rank head counts where tp divides them (the sharded
        # kernel's real tile); the global counts otherwise
        div = tp > 1 and n_heads % tp == 0 and n_kv_heads % tp == 0
        h_l = n_heads // tp if div else n_heads
        hkv_l = n_kv_heads // tp if div else n_kv_heads
        _, r = flash.decode_plan(slots, h_l, hkv_l, head_dim, page,
                                 pages_max, itemsize, kv_itemsize=kvi)
        reasons["attention"] = r
        _, r = flash.decode_plan(slots, h_l, hkv_l, head_dim, page,
                                 pages_max, itemsize, span=spec_tokens,
                                 kv_itemsize=kvi)
        reasons["spec"] = r
        _, r = flash.prefill_plan(h_l, hkv_l, head_dim, page, pages_max,
                                  itemsize, chunk=prefill_chunk,
                                  kv_itemsize=kvi)
        reasons["prefill"] = r
    return reasons


def accept_lengths(draft_ok) -> jax.Array:
    """Per-slot accepted-prefix length of a (slots, k) draft-match mask:
    the number of leading True entries — the speculative contract (a
    rejection invalidates every later draft, whose context included the
    rejected token). Works on host (numpy) or traced arrays."""
    ok = jnp.asarray(draft_ok, jnp.int32)
    return jnp.sum(jnp.cumprod(ok, axis=1), axis=1)


def note_serving_tokens(phase: str, n: int, accepted: bool = True) -> None:
    """Bump the per-session token-throughput counter
    ``accl_serving_tokens_total{phase, accepted}`` — ``phase`` in
    ``prefill | decode | verify``, ``accepted`` False for speculative
    drafts the verify epilogue rolled back.  The step wrappers count
    what they can know host-side for free (prefill chunk sizes,
    decode slot-steps, spec spans posted); the serving loop calls this
    with the EXACT accept/reject split once it reads the accept
    lengths back (it needs them anyway to schedule the next drafts)."""
    from ..obs import metrics
    metrics.inc("accl_serving_tokens_total", float(n),
                (("phase", phase),
                 ("accepted", "true" if accepted else "false")))


def _step_local(p: DecodeParams, state: DecodeState, x,
                overlap: Optional[bool], mesh_axes, wire_dtype,
                decode_mode: Optional[str]):
    """Per-rank decode step (inside shard_map): fused qkv projection →
    in-place KV append → paged decode attention over the rank's local
    heads → row-parallel output projection."""
    from ..ops import collective_matmul as cm
    from ..ops import flash

    tp = lax.axis_size(TP_AXIS)
    slots, d_model = x.shape
    hkv_l, _, _, hd = state.k_pages.shape        # local kv heads
    h_l = p.wq.shape[1] // hd                    # local q heads
    # one fused projection: the local column blocks [q | k | v] ride a
    # single all_gather_matmul when the plans engage (x is tp-replicated,
    # so its row shards ARE the ring's travelling blocks — mlp idiom)
    wqkv = jnp.concatenate([p.wq, p.wk, p.wv], axis=1)
    fused = (tp > 1 and slots % tp == 0
             and cm.agmm_engages(slots // tp, d_model, wqkv.shape[1], tp,
                                 x.dtype, overlap,
                                 wire_dtype=wire_dtype,
                                 w_dtype=wqkv.dtype)
             and cm.mmrs_engages(slots, h_l * hd, d_model, tp, x.dtype,
                                 overlap, wire_dtype=wire_dtype,
                                 w_dtype=p.wo.dtype))
    if fused:
        ms = slots // tp
        x_s = lax.dynamic_slice_in_dim(
            x, lax.axis_index(TP_AXIS) * ms, ms, axis=0)
        qkv = dapi.all_gather_matmul(x_s, wqkv, axis=TP_AXIS,
                                     mesh_axes=mesh_axes, overlap=overlap,
                                     wire_dtype=wire_dtype)
    else:
        qkv = jnp.dot(x, wqkv, preferred_element_type=jnp.float32)
    q, k_new, v_new = jnp.split(
        qkv, [h_l * hd, (h_l + hkv_l) * hd], axis=1)
    q = q.reshape(slots, h_l, hd).astype(x.dtype)
    k_new = k_new.reshape(slots, hkv_l, hd)
    v_new = v_new.reshape(slots, hkv_l, hd)

    # append FIRST so the current token attends itself (flash_decode's
    # contract); retired slots are masked — cache and length untouched.
    # Capacity is the APPEND's own guard now (round 18): a slot at
    # pages_max·page drops its write lane in-function instead of every
    # caller re-deriving the mask — a full slot stops advancing and
    # keeps answering over its full cache until the host retires it
    # (:func:`full_slots` is the admission loop's eviction signal)
    k_pages, v_pages, seq_lens = flash.kv_cache_append(
        state.k_pages, state.v_pages, state.block_tables, state.seq_lens,
        k_new, v_new, active=state.active)

    attn = flash.flash_decode(q, k_pages, v_pages, state.block_tables,
                              seq_lens, decode_mode=decode_mode)
    o = attn.reshape(slots, h_l * hd)

    if fused:
        y_s = dapi.matmul_reduce_scatter(o.astype(x.dtype), p.wo,
                                         axis=TP_AXIS,
                                         mesh_axes=mesh_axes,
                                         overlap=overlap,
                                         wire_dtype=wire_dtype)
        y = lax.all_gather(y_s, TP_AXIS, axis=0, tiled=True)
    else:
        y = lax.psum(jnp.dot(o, p.wo, preferred_element_type=jnp.float32),
                     TP_AXIS)
    # a retired slot contributes exact zeros (its attention is zeros at
    # length 0, but the projection bias-free matmul of a stale q row
    # must not leak either — mask on the slot flag)
    y = jnp.where(state.active[:, None], y.astype(x.dtype), 0)
    return y, DecodeState(k_pages, v_pages, state.block_tables, seq_lens,
                          state.active)


def build_decode_step(mesh: Mesh, overlap: Optional[bool] = None,
                      wire_dtype=None,
                      decode_mode: Optional[str] = None):
    """One jitted continuous-batching decode step over the tp mesh:
    ``step(params, state, x) -> (y, state')`` where ``x`` is (slots,
    d_model) — the current token's hidden state per slot — and ``y``
    its attention-block output (retired slots: zeros).

    Compiled ONCE per (slots, d_model, cache geometry): admission,
    retirement and cache growth are value changes (`block_tables` /
    ``seq_lens`` / ``active``), never shape changes. ``overlap`` /
    ``wire_dtype`` steer the tp projections' collective-matmul ride
    (None: session defaults); ``decode_mode`` pins the attention
    kernel's paged/unpaged resolution per call
    (None: ``ACCLConfig.flash_decode``).

    Host dispatch of every call is timed into the serving path
    histogram ``accl_latency_dispatch_seconds{path="decode"}`` and
    counted as ``slots`` slot-steps in ``accl_serving_tokens_total``
    (the capacity accounting — the serving loop refines with
    :func:`note_serving_tokens` where it knows the live count)."""
    from ..obs import metrics

    axes = tuple(mesh.axis_names)
    p_specs, s_specs = param_specs(), state_specs()

    def step(p, state, x):
        return _step_local(p, state, x, overlap, axes, wire_dtype,
                           decode_mode)

    jitted = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(p_specs, s_specs, P()),
        out_specs=(P(), s_specs),
        check_vma=False))

    def timed(p, state, x):
        t0 = metrics.tick()
        out = jitted(p, state, x)
        metrics.note_latency_dispatch("decode", t0)
        metrics.inc("accl_serving_tokens_total", float(x.shape[0]),
                    (("phase", "decode"), ("accepted", "true")))
        return out

    return timed


def _prefill_step_local(p: DecodeParams, state: DecodeState, x, slot,
                        live, overlap: Optional[bool], mesh_axes,
                        wire_dtype, prefill_mode: Optional[str]):
    """Per-rank chunked-prefill step (inside shard_map): fused qkv
    projection over the CHUNK's rows → flash_prefill writes the chunk's
    K/V straight into the slot's page chain and sweeps its causal
    attention → row-parallel output projection. The decode step's
    datapath with the slot axis traded for the chunk axis."""
    from ..ops import collective_matmul as cm
    from ..ops import flash

    tp = lax.axis_size(TP_AXIS)
    C, d_model = x.shape
    hkv_l, _, _, hd = state.k_pages.shape
    h_l = p.wq.shape[1] // hd
    wqkv = jnp.concatenate([p.wq, p.wk, p.wv], axis=1)
    fused = (tp > 1 and C % tp == 0
             and cm.agmm_engages(C // tp, d_model, wqkv.shape[1], tp,
                                 x.dtype, overlap, wire_dtype=wire_dtype,
                                 w_dtype=wqkv.dtype)
             and cm.mmrs_engages(C, h_l * hd, d_model, tp, x.dtype,
                                 overlap, wire_dtype=wire_dtype,
                                 w_dtype=p.wo.dtype))
    if fused:
        ms = C // tp
        x_s = lax.dynamic_slice_in_dim(
            x, lax.axis_index(TP_AXIS) * ms, ms, axis=0)
        qkv = dapi.all_gather_matmul(x_s, wqkv, axis=TP_AXIS,
                                     mesh_axes=mesh_axes, overlap=overlap,
                                     wire_dtype=wire_dtype)
    else:
        qkv = jnp.dot(x, wqkv, preferred_element_type=jnp.float32)
    q, k_new, v_new = jnp.split(
        qkv, [h_l * hd, (h_l + hkv_l) * hd], axis=1)
    q = q.reshape(C, h_l, hd).astype(x.dtype)
    out, k_pages, v_pages, seq_lens = flash.flash_prefill(
        q, k_new.reshape(C, hkv_l, hd), v_new.reshape(C, hkv_l, hd),
        state.k_pages, state.v_pages, state.block_tables, state.seq_lens,
        slot, live=live, prefill_mode=prefill_mode)
    o = out.reshape(C, h_l * hd)
    if fused:
        y_s = dapi.matmul_reduce_scatter(o.astype(x.dtype), p.wo,
                                         axis=TP_AXIS,
                                         mesh_axes=mesh_axes,
                                         overlap=overlap,
                                         wire_dtype=wire_dtype)
        y = lax.all_gather(y_s, TP_AXIS, axis=0, tiled=True)
    else:
        y = lax.psum(jnp.dot(o, p.wo, preferred_element_type=jnp.float32),
                     TP_AXIS)
    return y.astype(x.dtype), DecodeState(
        k_pages, v_pages, state.block_tables, seq_lens, state.active)


def build_prefill_step(mesh: Mesh, overlap: Optional[bool] = None,
                       wire_dtype=None,
                       prefill_mode: Optional[str] = None):
    """One jitted chunked-prefill step over the tp mesh:
    ``step(params, state, x, slot, live) -> (y, state')`` where ``x``
    is (chunk, d_model) — one page-granular chunk of ONE slot's prompt
    hidden states — ``slot`` the target slot (python int or int32
    scalar), and ``live`` (int, default = chunk) the number of real
    rows in a final partial chunk.  ``y`` is the chunk's attention-
    block output (rows past ``live``: padding, slice them away).

    Admission becomes: ``admit(state, slot)`` then one prefill step per
    chunk of the prompt — each chunk's K/V lands straight in the paged
    pools (bit-identical to a ``kv_cache_append`` token loop at
    ``kv_cache_dtype="off"``) and its causal attention covers every
    earlier chunk through the same block-table walk, so the first
    decode step starts from a REAL prompt with no monolithic unpaged
    cache ever materialized.  Compiled once per chunk geometry; chunks,
    slots and lengths are all value changes.  Dispatch is timed into
    ``accl_latency_dispatch_seconds{path="prefill"}``; tokens count
    into ``accl_serving_tokens_total{phase="prefill"}`` (the host-known
    ``live`` when given, else the chunk size)."""
    from ..obs import metrics

    axes = tuple(mesh.axis_names)
    p_specs, s_specs = param_specs(), state_specs()

    def step(p, state, x, slot, live):
        return _prefill_step_local(p, state, x, slot, live, overlap,
                                   axes, wire_dtype, prefill_mode)

    jitted = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(p_specs, s_specs, P(), P(), P()),
        out_specs=(P(), s_specs),
        check_vma=False))

    def timed(p, state, x, slot, live=None):
        n = x.shape[0] if live is None else live
        lv = jnp.asarray(x.shape[0] if live is None else live, jnp.int32)
        t0 = metrics.tick()
        out = jitted(p, state, x, jnp.asarray(slot, jnp.int32), lv)
        metrics.note_latency_dispatch("prefill", t0)
        if not isinstance(n, jax.Array):
            metrics.inc("accl_serving_tokens_total", float(n),
                        (("phase", "prefill"), ("accepted", "true")))
        return out

    return timed


def _spec_step_local(p: DecodeParams, state: DecodeState, x, draft_ok,
                     span: int, overlap: Optional[bool], mesh_axes,
                     wire_dtype, decode_mode: Optional[str]):
    """Per-rank speculative decode step (inside shard_map): k = span
    draft tokens' hidden states ride ONE fused qkv projection and ONE
    multi-query page sweep, with verify-and-accept in the epilogue —
    accepted-prefix lengths land back in ``seq_lens`` and every
    rejected token's page rows are restored BIT-exactly from the
    pre-append snapshot (the rollback is block-table-addressed value
    changes: no shape moves, the compiled-step invariant)."""
    from ..ops import collective_matmul as cm
    from ..ops import flash

    tp = lax.axis_size(TP_AXIS)
    slots, k_span, d_model = x.shape
    if k_span != span:
        raise ValueError(
            f"x span dim {k_span} != the step's compiled span {span}")
    hkv_l, _, page, hd = state.k_pages.shape
    h_l = p.wq.shape[1] // hd
    rows = slots * k_span
    wqkv = jnp.concatenate([p.wq, p.wk, p.wv], axis=1)
    x2 = x.reshape(rows, d_model)
    fused = (tp > 1 and rows % tp == 0
             and cm.agmm_engages(rows // tp, d_model, wqkv.shape[1], tp,
                                 x.dtype, overlap, wire_dtype=wire_dtype,
                                 w_dtype=wqkv.dtype)
             and cm.mmrs_engages(rows, h_l * hd, d_model, tp, x.dtype,
                                 overlap, wire_dtype=wire_dtype,
                                 w_dtype=p.wo.dtype))
    if fused:
        ms = rows // tp
        x_s = lax.dynamic_slice_in_dim(
            x2, lax.axis_index(TP_AXIS) * ms, ms, axis=0)
        qkv = dapi.all_gather_matmul(x_s, wqkv, axis=TP_AXIS,
                                     mesh_axes=mesh_axes, overlap=overlap,
                                     wire_dtype=wire_dtype)
    else:
        qkv = jnp.dot(x2, wqkv, preferred_element_type=jnp.float32)
    q, k_new, v_new = jnp.split(
        qkv, [h_l * hd, (h_l + hkv_l) * hd], axis=1)
    q = q.reshape(slots, k_span, h_l, hd).astype(x.dtype)
    k_new = k_new.reshape(slots, k_span, hkv_l, hd)
    v_new = v_new.reshape(slots, k_span, hkv_l, hd)

    # a slot must fit the WHOLE span or decline the step (the partial-
    # span horizon would lie about positions; full_slots is the
    # eviction signal) — declined slots neither write nor advance
    capacity = state.block_tables.shape[1] * page
    engaged = state.active & (state.seq_lens + k_span <= capacity)
    # rollback snapshot BEFORE the append: the page rows the span will
    # overwrite, captured in the POOL dtype so the restore is bit-exact
    saved_k, saved_v = flash.kv_cache_read_rows(
        state.k_pages, state.v_pages, state.block_tables, state.seq_lens,
        k_span)
    k_pages, v_pages, lens1 = flash.kv_cache_append_multi(
        state.k_pages, state.v_pages, state.block_tables, state.seq_lens,
        k_new, v_new, active=engaged)

    attn = flash.flash_decode_multi(q, k_pages, v_pages,
                                    state.block_tables, lens1,
                                    decode_mode=decode_mode)
    o = attn.reshape(rows, h_l * hd)
    if fused:
        y_s = dapi.matmul_reduce_scatter(o.astype(x.dtype), p.wo,
                                         axis=TP_AXIS,
                                         mesh_axes=mesh_axes,
                                         overlap=overlap,
                                         wire_dtype=wire_dtype)
        y = lax.all_gather(y_s, TP_AXIS, axis=0, tiled=True)
    else:
        y = lax.psum(jnp.dot(o, p.wo, preferred_element_type=jnp.float32),
                     TP_AXIS)
    y = y.reshape(slots, k_span, d_model)
    y = jnp.where(engaged[:, None, None], y.astype(x.dtype), 0)

    # verify-and-accept epilogue: the accepted PREFIX advances the slot,
    # the rejected tail's page rows roll back to the snapshot.  A
    # declined slot "accepts" the whole span of nothing — base + span
    # lands it back at its untouched length, and the rollback's
    # out-of-range guard drops its restore lanes
    accept = jnp.where(engaged, accept_lengths(draft_ok), k_span)
    k_pages, v_pages, seq_lens = flash.kv_cache_rollback(
        k_pages, v_pages, state.block_tables, lens1, saved_k, saved_v,
        accept, k_span)
    return y, DecodeState(k_pages, v_pages, state.block_tables, seq_lens,
                          state.active)


def build_spec_decode_step(mesh: Mesh, k: int,
                           overlap: Optional[bool] = None,
                           wire_dtype=None,
                           decode_mode: Optional[str] = None):
    """One jitted speculative multi-token decode step over the tp mesh:
    ``step(params, state, x, draft_ok) -> (y, state')`` where ``x`` is
    (slots, k, d_model) — k draft tokens' hidden states per slot — and
    ``draft_ok`` (slots, k) bool marks which drafts the serving loop's
    verifier matched.  ``y`` is (slots, k, d_model): the attention-
    block output at EVERY draft position (the verifier's logits source
    — exactly what k sequential decode steps would produce, bit-
    identically, since each row's causal horizon is its own position).

    The epilogue feeds the accepted-prefix lengths back into
    ``seq_lens`` and rolls the rejected tokens' KV page rows back to
    their pre-step bytes (block-table-addressed value changes — shapes
    never move, one compiled step per session stays the invariant).
    All-accept leaves the k appended tokens in place: the state is then
    bit-identical to k sequential ``build_decode_step`` steps, and
    ``k=1`` IS that step (pinned byte-identical — same kernel, same
    append, identity rollback).  Dispatch is timed into
    ``accl_latency_dispatch_seconds{path="verify"}``; draft tokens
    count into ``accl_serving_tokens_total{phase="verify"}`` with the
    accept/reject split when ``draft_ok`` is host-resident (else
    posted-as-accepted; the serving loop refines via
    :func:`note_serving_tokens`)."""
    from ..obs import metrics

    if k < 1:
        raise ValueError(f"spec decode span k must be >= 1, got {k}")
    axes = tuple(mesh.axis_names)
    p_specs, s_specs = param_specs(), state_specs()

    def step(p, state, x, draft_ok):
        return _spec_step_local(p, state, x, draft_ok, k, overlap, axes,
                                wire_dtype, decode_mode)

    jitted = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(p_specs, s_specs, P(), P()),
        out_specs=(P(), s_specs),
        check_vma=False))

    def timed(p, state, x, draft_ok):
        t0 = metrics.tick()
        out = jitted(p, state, x, draft_ok)
        metrics.note_latency_dispatch("verify", t0)
        if not isinstance(draft_ok, jax.Array):
            ok = np.asarray(draft_ok, bool)
            acc = int(np.sum(np.cumprod(ok, axis=1)))
            metrics.inc("accl_serving_tokens_total", float(acc),
                        (("phase", "verify"), ("accepted", "true")))
            metrics.inc("accl_serving_tokens_total", float(ok.size - acc),
                        (("phase", "verify"), ("accepted", "false")))
        else:
            metrics.inc("accl_serving_tokens_total", float(x.shape[0] * k),
                        (("phase", "verify"), ("accepted", "true")))
        return out

    return timed


def spec_step_reference(p: DecodeParams, state: DecodeState, x, draft_ok):
    """Single-device oracle of one speculative step — the unpaged
    datapath over unsharded params/state: dense projections, multi-
    token append, gathered-chain attention with per-row horizons,
    verify/rollback epilogue. Same math as the sharded program."""
    from ..ops import flash

    slots, k_span, d_model = x.shape
    hkv, _, page, hd = state.k_pages.shape
    h = p.wq.shape[1] // hd
    x2 = x.reshape(slots * k_span, d_model)
    q = jnp.dot(x2, p.wq, preferred_element_type=jnp.float32)
    k_new = jnp.dot(x2, p.wk, preferred_element_type=jnp.float32)
    v_new = jnp.dot(x2, p.wv, preferred_element_type=jnp.float32)
    capacity = state.block_tables.shape[1] * page
    engaged = state.active & (state.seq_lens + k_span <= capacity)
    saved_k, saved_v = flash.kv_cache_read_rows(
        state.k_pages, state.v_pages, state.block_tables, state.seq_lens,
        k_span)
    k_pages, v_pages, lens1 = flash.kv_cache_append_multi(
        state.k_pages, state.v_pages, state.block_tables, state.seq_lens,
        k_new.reshape(slots, k_span, hkv, hd),
        v_new.reshape(slots, k_span, hkv, hd), active=engaged)
    attn = flash.flash_decode_multi(
        q.reshape(slots, k_span, h, hd).astype(x.dtype), k_pages,
        v_pages, state.block_tables, lens1, decode_mode="unpaged")
    y = jnp.dot(attn.reshape(slots * k_span, h * hd), p.wo,
                preferred_element_type=jnp.float32)
    y = y.reshape(slots, k_span, d_model)
    y = jnp.where(engaged[:, None, None], y.astype(x.dtype), 0)
    accept = jnp.where(engaged, accept_lengths(draft_ok), k_span)
    k_pages, v_pages, seq_lens = flash.kv_cache_rollback(
        k_pages, v_pages, state.block_tables, lens1, saved_k, saved_v,
        accept, k_span)
    return y, DecodeState(k_pages, v_pages, state.block_tables, seq_lens,
                          state.active)


def decode_step_reference(p: DecodeParams, state: DecodeState, x):
    """Single-device oracle of one decode step — same math as the
    sharded program (fused or baseline datapath): dense qkv projection,
    masked append, unpaged attention over the gathered chains, dense
    output projection. Operates on UNSHARDED (global) params/state."""
    from ..ops import flash

    slots = x.shape[0]
    hkv, _, page, hd = state.k_pages.shape
    h = p.wq.shape[1] // hd
    q = jnp.dot(x, p.wq, preferred_element_type=jnp.float32)
    k_new = jnp.dot(x, p.wk, preferred_element_type=jnp.float32)
    v_new = jnp.dot(x, p.wv, preferred_element_type=jnp.float32)
    k_pages, v_pages, seq_lens = flash.kv_cache_append(
        state.k_pages, state.v_pages, state.block_tables, state.seq_lens,
        k_new.reshape(slots, hkv, hd),
        v_new.reshape(slots, hkv, hd), active=state.active)
    attn = flash.flash_decode(
        q.reshape(slots, h, hd).astype(x.dtype), k_pages, v_pages,
        state.block_tables, seq_lens, decode_mode="unpaged")
    y = jnp.dot(attn.reshape(slots, h * hd), p.wo,
                preferred_element_type=jnp.float32)
    y = jnp.where(state.active[:, None], y.astype(x.dtype), 0)
    return y, DecodeState(k_pages, v_pages, state.block_tables, seq_lens,
                          state.active)


# ---------------------------------------------------------------------------
# serving-tier token traffic (the latency tier's consumer)
# ---------------------------------------------------------------------------

def publish_tokens(acc, tokens, src: int, tag: int = 0, comm=None):
    """Fan one decode step's sampled token ids out from rank ``src`` to
    every other rank as token-sized **eager** messages — the
    disaggregated-serving pattern (the sampling rank owns the logits;
    every rank needs the ids to append next step), and exactly the
    bursty sub-threshold traffic the round-13 latency tier serves: each
    send is a single rx-buffer segment riding the eager fast path
    (timed into ``accl_latency_dispatch_seconds{path="eager_send"}``),
    with rx-pool slots as the backpressure when receivers lag.

    ``tokens``: (slots,) int32 host array/list. Returns the list of
    per-destination received arrays (each == ``tokens``). Sends are
    posted as one burst FIRST, then matched by the recvs — world-1
    concurrent parked token messages, the rxpool occupancy shape of a
    real decode fleet."""
    tokens = np.asarray(tokens, np.int32)
    n = tokens.shape[0]
    comm = comm or acc.global_comm()
    world = comm.world_size
    sbuf = acc.create_buffer(n, dataType.int32)
    sbuf.host[src] = tokens
    dsts = [d for d in range(world) if d != src]
    for dst in dsts:                       # the burst: all posts park
        acc.send(sbuf, n, src=src, dst=dst, tag=tag, comm=comm)
    out = []
    for dst in dsts:
        rbuf = acc.create_buffer(n, dataType.int32)
        acc.recv(rbuf, n, src=src, dst=dst, tag=tag, comm=comm)
        out.append(np.asarray(rbuf.host[dst]))
    return out


def pack_token_records(sessions) -> np.ndarray:
    """Flatten multiple sessions' token vectors into ONE int32 record
    stream: ``[n_sessions, (sid, count, tokens...)...]`` — the batched
    fan-out's wire format.  ``sessions``: dict ``{session_id: tokens}``
    or iterable of ``(session_id, tokens)`` pairs."""
    items = sessions.items() if hasattr(sessions, "items") else sessions
    items = [(int(s), np.asarray(t, np.int32).reshape(-1))
             for s, t in items]
    recs = [np.asarray([len(items)], np.int32)]
    for sid, toks in items:
        recs.append(np.asarray([sid, toks.shape[0]], np.int32))
        recs.append(toks)
    return np.concatenate(recs)


def unpack_token_records(flat) -> dict:
    """Inverse of :func:`pack_token_records`: the per-session token
    dict a receiver reads back out of one batched message."""
    flat = np.asarray(flat, np.int32)
    n, i, out = int(flat[0]), 1, {}
    for _ in range(n):
        sid, cnt = int(flat[i]), int(flat[i + 1])
        out[sid] = flat[i + 2:i + 2 + cnt].copy()
        i += 2 + cnt
    return out


def publish_tokens_batch(acc, sessions, src: int, tag: int = 0,
                         comm=None):
    """Fan MULTIPLE sessions' sampled tokens out from rank ``src`` in
    ONE sub-threshold eager send per (src, dst) pair — the batched
    :func:`publish_tokens`: where a per-session loop posts
    ``n_sessions`` messages per destination (each parking its own
    rx-pool slot, each paying its own dispatch), the batch packs the
    records (:func:`pack_token_records`) into a single token-sized
    message, so the match engine sees ONE send_parked/recv_matched pair
    per destination per decode step regardless of how many sessions
    published.  Returns the per-destination list of unpacked
    ``{session_id: tokens}`` dicts (each == the input)."""
    flat = pack_token_records(sessions)
    n = flat.shape[0]
    comm = comm or acc.global_comm()
    world = comm.world_size
    sbuf = acc.create_buffer(n, dataType.int32)
    sbuf.host[src] = flat
    dsts = [d for d in range(world) if d != src]
    for dst in dsts:                       # one burst, one post per dst
        acc.send(sbuf, n, src=src, dst=dst, tag=tag, comm=comm)
    out = []
    for dst in dsts:
        rbuf = acc.create_buffer(n, dataType.int32)
        acc.recv(rbuf, n, src=src, dst=dst, tag=tag, comm=comm)
        out.append(unpack_token_records(rbuf.host[dst]))
    return out


# ---------------------------------------------------------------------------
# session handoff entry points (the disaggregated-serving datapath)
# ---------------------------------------------------------------------------

def used_pages(state: DecodeState, slot: int) -> int:
    """Host-side page count of ``slot``'s live chain:
    ``ceil(seq_len / page)`` — what a handoff must ship."""
    page = state.k_pages.shape[2]
    return -(-int(state.seq_lens[slot]) // page)


def extract_session(state: DecodeState, slot: int):
    """Read ``slot``'s session out of the pools for a handoff /
    migration: ``(k_rows, v_rows, length)`` with the rows
    (H_kv, used, page, hd) in the POOL's at-rest dtype — int8 sessions
    ship 1-byte pages, and the install is bit-exact because the bytes
    never round-trip through a dequant.  Host-driven (``slot`` is a
    python int, ``length`` comes back as one)."""
    from ..ops import flash

    length = int(state.seq_lens[slot])
    if length <= 0:
        raise ValueError(f"slot {slot} has no live session to extract")
    k_rows, v_rows = flash.kv_cache_extract_pages(
        state.k_pages, state.v_pages, state.block_tables, slot,
        used_pages(state, slot))
    return k_rows, v_rows, length


def install_session(state: DecodeState, slot: int, k_rows, v_rows,
                    length: int) -> DecodeState:
    """Land a handed-off session in ``slot``: the received page rows
    are written into the pool pages the slot's block-table row names
    (:func:`flash.kv_cache_install_pages` — dtype-checked, a codec
    mismatch raises rather than casts), the table row is committed
    back, and ``seq_lens``/``active`` advance to the session's length —
    the receiver-side block-table rewrite.  After this, decoding from
    ``slot`` is bit-exact with having prefilled the session here."""
    from ..ops import flash

    k_pages, v_pages = flash.kv_cache_install_pages(
        state.k_pages, state.v_pages, state.block_tables, slot,
        k_rows, v_rows)
    row = state.block_tables[slot]
    return DecodeState(
        k_pages, v_pages,
        state.block_tables.at[slot].set(row),
        state.seq_lens.at[slot].set(jnp.asarray(length, jnp.int32)),
        state.active.at[slot].set(True))
