"""ZeRO-style fully-sharded data parallelism (the FSDP family).

Two generations of the same idea live here:

* the original **flat-ravel demo** (:func:`build_zero_train_step`): one
  monolithic ``lax.all_gather`` of the whole parameter vector, compute,
  one monolithic ``lax.psum_scatter`` of the whole gradient — zero
  comm/compute overlap.  It remains as the parity oracle and the
  honest committed fallback of the layerwise step;
* the **layerwise overlapped step** (:func:`build_zero_fsdp_train_step`),
  ZeRO-3/FSDP (Rajbhandari et al.) rebuilt on the fused comm×compute
  kernel family so FSDP's communication *is* the kernels:

  - each layer's matmul-weight shards travel the ring of
    ``all_gather_matmul`` — the agmm kernel IS FSDP's forward: every
    arriving parameter shard's output block is computed while the next
    hop's remote DMA is in flight, and the full weight never
    materializes in one buffer (the shard is stored pre-transposed in
    "travel layout" so no per-step transposes are paid);
  - the gradient reduction IS ``matmul_reduce_scatter``: the agmm
    ``custom_vjp``'s dual kernel delivers each rank ONLY its shard of
    the dp-summed weight gradient (ZeRO's defining move), with the
    backward parameter RE-gather folded into dx's contraction by the
    round-9 fused wgrad kernel;
  - the attention projections ride the SAME agmm family: Wqkvᵀ and
    Woᵀ are stored as travel shards (the decode step's fused-qkv shape
    ported back into training), so with plans engaged the whole step
    traces ZERO unfused collectives.  When the attention plans alone
    decline (:func:`fsdp_attn_engage_reason`), the travel blocks
    gather per layer with **cross-layer prefetch**: layer l+1's
    ``all_gather`` is issued under layer l's compute — the
    double-buffered two-slot schedule, the ``pallas_chunked`` credit
    idiom lifted to the schedule level (two gathered layers live at
    any time; XLA's latency-hiding scheduler overlaps the independent
    collective), the decline counted once.  That leg's GRADIENT rides
    the wire bucketized and compressed via the ``cmatmul_wire_dtype``
    machinery (bf16 / bf16_sr; rounded once before the wire —
    tolerance-bounded like the mm×rs travelling accumulator).

The flagship workload is a multi-layer transformer-block train step
(attention via ``ops/flash.py``, MLP via the collective-matmul family)
over a (dp × tp) mesh: ZeRO shards every parameter 1/dp along the dp
axis, Megatron splits heads/hidden along tp, and the whole
forward + backward + Adam runs as ONE jitted shard_map program — the
first program composing flash, cmatmul and the wire codecs.

Plan-policy honesty (the mlp/moe discipline): the layerwise step
COMMITS to the fused datapath only when the per-layer kernel plans all
engage (session registers + VMEM plans + rung —
:func:`fsdp_engage_reason`); anything less runs the flat-ravel baseline
schedule unchanged — never a degraded unfused rendition of the
layerwise program — counted under
``accl_cmatmul_fallback_total{op="zero_fsdp"}``.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import fault as _fault
from ..communicator import Communicator
from ..obs import metrics as _metrics
from ..parallel.primitives import AXIS, _smap
from . import mlp
from .mlp import DP_AXIS, TP_AXIS, make_mesh  # noqa: F401  (re-export)

#: the fallback-counter op label of the layerwise step's committed
#: baseline (accl_cmatmul_fallback_total{op="zero_fsdp"})
FSDP_OP = "zero_fsdp"


# ---------------------------------------------------------------------------
# session registers (ACCLConfig.zero_overlap / zero_prefetch write-through,
# the cmatmul_overlap shape); per-call override on the builder
# ---------------------------------------------------------------------------

_OVERLAP_DEFAULT = True
_PREFETCH_DEFAULT = True
_REPLICAS_DEFAULT = False


def set_overlap_enabled(enabled: bool) -> None:
    """Set the module-default overlap mode (``ACCLConfig.zero_overlap``
    lands here at every config assignment). Per-call override: the
    builder's ``overlap`` argument."""
    global _OVERLAP_DEFAULT
    _OVERLAP_DEFAULT = bool(enabled)


def get_overlap_enabled() -> bool:
    return _OVERLAP_DEFAULT


def set_prefetch_enabled(enabled: bool) -> None:
    """Set the module-default cross-layer prefetch mode
    (``ACCLConfig.zero_prefetch`` write-through)."""
    global _PREFETCH_DEFAULT
    _PREFETCH_DEFAULT = bool(enabled)


def get_prefetch_enabled() -> bool:
    return _PREFETCH_DEFAULT


def set_replicas_enabled(enabled: bool) -> None:
    """Set the module-default buddy-replication mode
    (``ACCLConfig.shard_replicas`` write-through). Per-call override:
    the ``replicate`` argument of :func:`build_zero_train_step`."""
    global _REPLICAS_DEFAULT
    _REPLICAS_DEFAULT = bool(enabled)


def get_replicas_enabled() -> bool:
    return _REPLICAS_DEFAULT


# ===========================================================================
# the original flat-ravel demo (single MLP, 1-D communicator axis)
# ===========================================================================


class ZeroState(NamedTuple):
    """Per-rank shards of the flat parameter/optimizer vectors, plus the
    replicated Adam step counter. Global shapes: (world, n_pad/world)."""

    w: jax.Array
    m: jax.Array
    v: jax.Array
    t: jax.Array  # () int32, replicated


class ZeroReplica(NamedTuple):
    """Buddy replicas of the ZeRO state (docs/resilience.md §5): row ``r``
    holds rank ``(r − 1) % world``'s shards — each rank mirrors its shard
    to its RING SUCCESSOR (``fault.buddy_rank``), so after a single rank
    loss the dead rank's state survives on its buddy and
    :func:`restore_zero_state` re-materializes it. Same global shapes and
    sharding as the state shards they mirror."""

    w: jax.Array
    m: jax.Array
    v: jax.Array


# -- multi-process-safe array construction -----------------------------------
#
# jax.device_put(full_np, sharding) requires every shard to be process-
# addressable; on the multi-controller rung each process may only place
# its own rows. These helpers build the same global arrays on both rungs
# (every process computes the identical host value — the SPMD discipline
# the session nonce handshake already assumes).


def put_rows(comm: Communicator, rows: np.ndarray) -> jax.Array:
    """Place a host ``(world, ...)`` array one-row-per-rank over the
    communicator (axis 0 sharded, the ``comm.sharding()`` layout), on
    either rung: plain ``device_put`` single-controller, per-local-rank
    shard assembly multi-controller."""
    if not comm.is_multiprocess:
        return jax.device_put(rows, comm.sharding())
    shards = [jax.device_put(rows[r:r + 1], comm.device(r))
              for r in comm.local_ranks]
    return jax.make_array_from_single_device_arrays(
        rows.shape, comm.sharding(), shards)


def put_replicated_scalar(comm: Communicator, value) -> jax.Array:
    """A replicated () scalar usable as a ``P()`` shard_map operand on
    both rungs (the Adam step counter)."""
    val = np.asarray(value, np.int32)
    if not comm.is_multiprocess:
        return jnp.asarray(val)
    return jax.make_array_from_callback(
        (), comm.replicated_sharding(), lambda idx: val)


def _local_row(arr: jax.Array, rank: int) -> np.ndarray:
    """This process's host copy of row ``rank`` of a (world, ...) axis-0
    sharded array; raises when the rank's shard lives on another
    controller."""
    for s in arr.addressable_shards:
        idx = s.index[0]
        if (idx.start or 0) == rank:
            return np.asarray(s.data)[0]
    raise ValueError(f"rank {rank}'s shard is not addressable on this "
                     f"process")


def _scalar_value(t) -> np.ndarray:
    try:
        return np.asarray(t.addressable_shards[0].data)
    except (AttributeError, IndexError):
        return np.asarray(t)


@functools.lru_cache(maxsize=None)
def _template(d_model: int, d_hidden: int) -> Tuple[int, Callable]:
    """(flat length, unravel) for the MLP parameter pytree — cached per
    geometry so the throwaway sizing init runs at most once per process
    (init_zero_state derives its own from the real init and never calls
    this)."""
    p = mlp.init_params(jax.random.PRNGKey(0), d_model, d_hidden)
    vec, unravel = ravel_pytree(p)
    return vec.shape[0], unravel


def init_zero_state(key, comm: Communicator, d_model: int,
                    d_hidden: int) -> ZeroState:
    """Initialize parameters and shard them (with zeroed Adam moments)
    across the communicator — 1/world of every vector per rank."""
    world = comm.world_size
    vec, _ = ravel_pytree(mlp.init_params(key, d_model, d_hidden))
    n = vec.shape[0]
    pad = (-n) % world
    flat = np.concatenate([np.asarray(vec), np.zeros(pad, np.float32)])
    shards = flat.reshape(world, -1)
    return ZeroState(
        w=put_rows(comm, shards),
        m=put_rows(comm, np.zeros_like(shards)),
        v=put_rows(comm, np.zeros_like(shards)),
        t=put_replicated_scalar(comm, 0),
    )


def build_zero_train_step(comm: Communicator, d_model: int, d_hidden: int,
                          lr: float = 1e-2, b1: float = 0.9,
                          b2: float = 0.999, eps: float = 1e-8,
                          replicate: Optional[bool] = None,
                          replica_wire_dtype="off"):
    """``step(state, x, y) -> (state, loss)`` — one fused ZeRO step.

    ``x``/``y``: (world, batch, d_model) global arrays, batch sharded
    over the communicator axis (pure dp; compose with the tp MLP for 2-D).

    ``replicate`` (None → the ``ACCLConfig.shard_replicas`` session
    register) piggybacks a **buddy-replica write** on the step: after the
    optimizer update, each rank's fresh shards ride ONE ``ppermute`` to
    the ring successor inside the same compiled program (no extra
    launch), and the step returns ``(state, loss, ZeroReplica)``. The
    replica is what :func:`restore_zero_state` rebuilds a lost rank's
    state from after a survivor-subset recovery. ``replica_wire_dtype``
    stages the mirror hop through the existing cmatmul codecs ("off" —
    the default — keeps it full precision, so restores are bit-exact;
    "bf16"/"bf16_sr" halve the wire at a tolerance-bounded replica;
    None follows the session ``cmatmul_wire_dtype`` register)."""
    world = comm.world_size
    n, unravel = _template(d_model, d_hidden)
    do_replicate = (_REPLICAS_DEFAULT if replicate is None
                    else bool(replicate))
    if do_replicate:
        perm = [(i, _fault.buddy_rank(i, world)) for i in range(world)]
        _metrics.inc("accl_zero_replica_total",
                     labels=(("event", "write"),))

        def _mirror(arr):
            from ..ops import collective_matmul as cm
            wdt, sr = cm._resolve_wire_codec(replica_wire_dtype, arr.dtype)
            staged = cm._wire_cast(arr, wdt, stochastic=sr)
            return lax.ppermute(staged, AXIS, perm).astype(arr.dtype)

    def body(w, m, v, t, x, y):
        w, m, v = w[0], m[0], v[0]          # (n_pad/world,) local shards
        x, y = x[0], y[0]                   # (batch, d) local batch
        full = lax.all_gather(w, AXIS, tiled=True)     # (n_pad,)
        params = unravel(full[:n])

        def loss_fn(p):
            return jnp.mean((mlp.apply(p, x) - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        gvec = ravel_pytree(grads)[0]
        pad = w.shape[0] * world - n
        if pad:
            # divisible geometries skip the traced concat entirely — the
            # common case pays no copy for padding it does not need
            gvec = jnp.concatenate(
                [gvec, jnp.zeros((pad,), gvec.dtype)])
        # dp-reduce AND shard in one collective: each rank receives only
        # its slice of the mean gradient (ZeRO's defining move)
        gsh = lax.psum_scatter(gvec, AXIS, tiled=True) / world

        t_new = t + 1
        m_new = b1 * m + (1 - b1) * gsh
        v_new = b2 * v + (1 - b2) * gsh * gsh
        mhat = m_new / (1 - b1 ** t_new.astype(jnp.float32))
        vhat = v_new / (1 - b2 ** t_new.astype(jnp.float32))
        w_new = w - lr * mhat / (jnp.sqrt(vhat) + eps)
        loss = lax.psum(loss, AXIS) / world
        if do_replicate:
            # the buddy write piggybacks on the step program: the fresh
            # shards ride one ppermute to the ring successor while XLA's
            # scheduler overlaps it with the loss psum — rank r's output
            # replica row holds rank (r-1)%world's new shards
            rw, rm, rv = _mirror(w_new), _mirror(m_new), _mirror(v_new)
            return (w_new[None], m_new[None], v_new[None], t_new, loss,
                    rw[None], rm[None], rv[None])
        return (w_new[None], m_new[None], v_new[None], t_new, loss)

    n_out = 8 if do_replicate else 5
    prog = _smap(
        comm, body, 6,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(), P(AXIS), P(AXIS)),
        out_specs=tuple([P(AXIS), P(AXIS), P(AXIS), P(), P()]
                        + [P(AXIS)] * (n_out - 5)),
    )

    def step(state: ZeroState, x, y):
        out = prog(state.w, state.m, state.v, state.t, x, y)
        w, m, v, t, loss = out[:5]
        if do_replicate:
            return ZeroState(w, m, v, t), loss, ZeroReplica(*out[5:])
        return ZeroState(w, m, v, t), loss

    return step


def gather_params(state: ZeroState, comm: Communicator, d_model: int,
                  d_hidden: int) -> mlp.MLPParams:
    """Materialize the full parameter pytree from the shards (host-side
    convenience for eval/checkpointing).

    HOST-side by construction: every shard must be process-addressable.
    Under multi-process execution some shards live on other hosts, where
    the old ``np.asarray`` path failed with an opaque runtime error —
    now rejected up front with the remediation in the message."""
    n, unravel = _template(d_model, d_hidden)
    if not getattr(state.w, "is_fully_addressable", True):
        raise NotImplementedError(
            "gather_params assembles shards on the host, which requires "
            "every shard to be process-addressable; this array spans "
            "non-addressable devices (multi-process mesh). Gather on "
            "device instead (a jitted lax.all_gather over the mesh axis) "
            "or save per-rank shards.")
    flat = np.asarray(state.w).reshape(-1)[:n]
    return unravel(jnp.asarray(flat))


# ===========================================================================
# buddy replication + survivor-subset restore (docs/resilience.md §5)
# ===========================================================================


def build_buddy_replicate(comm: Communicator, wire_dtype="off"):
    """``replicate(state) -> ZeroReplica`` — one compiled program
    mirroring each rank's (w, m, v) shards to its ring successor
    (``fault.buddy_rank``) in a single ``ppermute`` per tensor. The
    standalone form of the piggybacked write in
    :func:`build_zero_train_step` — used to seed the replica before the
    first step (a rank that dies at step 0 is still restorable) and to
    re-seed it right after a restore. ``wire_dtype`` as on the step
    builder ("off" = full precision, bit-exact restores)."""
    world = comm.world_size
    perm = [(i, _fault.buddy_rank(i, world)) for i in range(world)]
    _metrics.inc("accl_zero_replica_total", labels=(("event", "write"),))

    def body(w, m, v):
        from ..ops import collective_matmul as cm

        def mirror(arr):
            a = arr[0]
            wdt, sr = cm._resolve_wire_codec(wire_dtype, a.dtype)
            staged = cm._wire_cast(a, wdt, stochastic=sr)
            return lax.ppermute(staged, AXIS, perm).astype(a.dtype)[None]

        return mirror(w), mirror(m), mirror(v)

    prog = _smap(comm, body, 3,
                 out_specs=(P(AXIS), P(AXIS), P(AXIS)))

    def replicate(state: ZeroState) -> ZeroReplica:
        return ZeroReplica(*prog(state.w, state.m, state.v))

    return replicate


def restore_zero_state(new_comm: Communicator, state: ZeroState,
                       replica: ZeroReplica, survivors, dead,
                       n: int) -> ZeroState:
    """Re-materialize the ZeRO state on the SURVIVOR mesh after a true
    rank loss — training resumes without a host checkpoint.

    ``new_comm`` is the shrunk communicator (``ACCL.recover()`` shrink
    mode rebuilt it over the survivor indices); ``survivors``/``dead``
    are OLD rank indices (``fault.survivors_of`` order = new rank
    order); ``n`` the unpadded flat parameter length
    (``zero._template(d_model, d_hidden)[0]``). Every surviving
    controller calls this SPMD, like any collective.

    Protocol: each survivor contributes its own (w, m, v) shards plus
    the replica rows it holds; one all-gather over the NEW mesh (the
    recovered datapath, not the dead one) replicates all contributions;
    each dead rank's shard is then read off its ring successor's replica
    (``fault.replica_holders`` — raising when the buddy also died, the
    single-failure guarantee), the full flat vectors are reassembled
    bit-exactly (full-precision replicas) and re-partitioned over the
    smaller dp axis. Counted ``accl_zero_replica_total{event="restore"}``.
    """
    survivors = list(survivors)
    dead = list(dead)
    P_old = len(survivors) + len(dead)
    holders = _fault.replica_holders(dead, P_old)
    nshard = state.w.shape[1]
    dtype = np.dtype(state.w.dtype)

    # per-new-rank contribution: [own w, m, v ‖ replica w, m, v]
    rows = np.zeros((new_comm.world_size, 6, nshard), dtype)
    for j in new_comm.local_ranks:
        r = survivors[j]
        for t_i, (own, rep) in enumerate(
                zip((state.w, state.m, state.v), replica)):
            rows[j, t_i] = _local_row(own, r)
            rows[j, 3 + t_i] = _local_row(rep, r)
    contrib = put_rows(new_comm, rows)

    # one all-gather over the SURVIVOR mesh replicates every contribution
    gather = _smap(
        new_comm,
        lambda v: lax.all_gather(v[0], AXIS, axis=0, tiled=False),
        1, out_specs=P())
    gathered = np.asarray(gather(contrib).addressable_shards[0].data)

    full = np.zeros((3, P_old, nshard), dtype)
    for j, r in enumerate(survivors):
        full[:, r] = gathered[j, :3]
    for k, b in holders.items():
        full[:, k] = gathered[survivors.index(b), 3:]

    P_new = len(survivors)
    pad = (-n) % P_new
    repart = []
    for t_i in range(3):
        flat = full[t_i].reshape(-1)[:n]
        if pad:
            flat = np.concatenate([flat, np.zeros(pad, flat.dtype)])
        repart.append(put_rows(new_comm, flat.reshape(P_new, -1)))
    _metrics.inc("accl_zero_replica_total", labels=(("event", "restore"),))
    return ZeroState(
        w=repart[0], m=repart[1], v=repart[2],
        t=put_replicated_scalar(new_comm, _scalar_value(state.t)))


# ===========================================================================
# layerwise overlapped ZeRO/FSDP — the transformer-block flagship
# ===========================================================================


class FSDPParams(NamedTuple):
    """Per-layer ZeRO shards over a (dp, tp) mesh, one entry per layer.
    EVERY matrix — attention included — is stored in agmm travel
    layout, so each device's block IS the fused kernel's travelling
    shard and the forward contains no unfused parameter gather.

    * ``wqkvt``: (tp·q_rows_pad, d_model) — Wqkvᵀ per tp rank (the
      fused [q‖k‖v] column block of that rank, transposed; rows padded
      3·dtp → q_rows_pad for dp divisibility), rows split tp-major
      then dp (spec ``P((tp, dp), None)``) — the w1t shape.
    * ``wot``: (d_model, d_model) — Woᵀ; rows dp, cols tp (spec
      ``P(dp, tp)``) — the w2t shape: each device holds the travelling
      row block of its tp rank's Woᵀ column slice.
    * ``w1t``: (d_hidden, d_model) — W1ᵀ in travel layout; rows split
      tp-major then dp (spec ``P((tp, dp), None)``), so each device's
      block IS the agmm travelling shard of its tp column block.
    * ``w2t``: (d_model, d_hidden) — W2ᵀ in travel layout; rows dp,
      cols tp (spec ``P(dp, tp)``).
    """

    wqkvt: Tuple[jax.Array, ...]
    wot: Tuple[jax.Array, ...]
    w1t: Tuple[jax.Array, ...]
    w2t: Tuple[jax.Array, ...]


class ZeroFSDPState(NamedTuple):
    p: FSDPParams
    m: FSDPParams
    v: FSDPParams
    t: jax.Array  # () int32, replicated


def _attn_sizes(d_model: int, tp: int) -> Tuple[int, int]:
    """(dtp, n_attn): per-tp-rank attention column width d/tp and the
    unpadded flat bucket length 4·d·dtp (Wqkv (d, 3·dtp) + Wo (dtp, d))
    — the pipeline stack's bucket layout (``models/pipeline.py``); the
    FSDP step itself stores attention in travel layout
    (:func:`_attn_travel_sizes`)."""
    dtp = d_model // tp
    return dtp, 4 * d_model * dtp


def _attn_travel_sizes(d_model: int, tp: int,
                       dp: int) -> Tuple[int, int, int]:
    """(dtp, q_rows, q_rows_pad): per-tp-rank column width d/tp, the
    Wqkvᵀ travel row count 3·dtp, and that count padded up for dp
    divisibility (the agmm shard geometry — pad rows are zero and
    their outputs are sliced off before attention)."""
    dtp = d_model // tp
    q_rows = 3 * dtp
    return dtp, q_rows, q_rows + (-q_rows) % dp


def fsdp_param_specs(n_layers: int) -> FSDPParams:
    per = lambda s: tuple(s for _ in range(n_layers))
    return FSDPParams(
        wqkvt=per(P((TP_AXIS, DP_AXIS), None)),
        wot=per(P(DP_AXIS, TP_AXIS)),
        w1t=per(P((TP_AXIS, DP_AXIS), None)),
        w2t=per(P(DP_AXIS, TP_AXIS)),
    )


def _validate_geometry(dp: int, tp: int, d_model: int, d_hidden: int,
                       n_heads: int) -> None:
    if d_model % n_heads:
        raise ValueError(f"d_model {d_model} % n_heads {n_heads} != 0")
    if n_heads % tp or d_model % tp or d_hidden % tp:
        raise ValueError(
            f"tp {tp} must divide n_heads {n_heads}, d_model {d_model} "
            f"and d_hidden {d_hidden}")
    if (d_hidden // tp) % dp or d_model % dp:
        raise ValueError(
            f"dp {dp} must divide the tp-local hidden {d_hidden // tp} "
            f"and d_model {d_model} (the ZeRO column shards)")


def init_zero_fsdp(key, mesh, n_layers: int, d_model: int, d_hidden: int,
                   n_heads: int) -> ZeroFSDPState:
    """Initialize the L-layer transformer block stack and shard every
    parameter 1/dp across the mesh's dp axis (travel layout for the
    matmul weights, flat buckets for attention), with zeroed Adam
    moments — no rank ever holds a full optimizer state."""
    dp, tp = mesh.shape[DP_AXIS], mesh.shape[TP_AXIS]
    _validate_geometry(dp, tp, d_model, d_hidden, n_heads)
    dtp, q_rows, q_rows_pad = _attn_travel_sizes(d_model, tp, dp)
    s_attn = d_model ** -0.5
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_hidden) ** 0.5

    wqkvt, wot, w1t, w2t = [], [], [], []
    for lk in jax.random.split(key, n_layers):
        kq, kk, kv, ko, k1, k2 = jax.random.split(lk, 6)
        wq, wk, wv = (np.asarray(jax.random.normal(
            kx, (d_model, d_model), jnp.float32)) * s_attn
            for kx in (kq, kk, kv))
        wo = np.asarray(jax.random.normal(
            ko, (d_model, d_model), jnp.float32)) * s_attn
        rows = []
        for s in range(tp):
            cols = slice(s * dtp, (s + 1) * dtp)
            wqkv_s = np.concatenate([wq[:, cols], wk[:, cols], wv[:, cols]],
                                    axis=1)              # (d, 3·dtp)
            blk = np.ascontiguousarray(wqkv_s.T)         # (3·dtp, d) travel
            if q_rows_pad != q_rows:
                blk = np.concatenate(
                    [blk, np.zeros((q_rows_pad - q_rows, d_model),
                                   np.float32)])
            rows.append(blk)
        wqkvt.append(np.concatenate(rows))      # (tp·q_rows_pad, d) travel
        wot.append(np.ascontiguousarray(wo.T))  # (d, d) = Woᵀ travel
        w1 = np.asarray(jax.random.normal(
            k1, (d_model, d_hidden), jnp.float32)) * s1
        w2 = np.asarray(jax.random.normal(
            k2, (d_hidden, d_model), jnp.float32)) * s2
        w1t.append(np.ascontiguousarray(w1.T))           # (h, d) travel
        w2t.append(np.ascontiguousarray(w2.T))           # (d, h) travel

    specs = fsdp_param_specs(n_layers)
    # every process computed the identical full host value above, so
    # each can place its own shards locally — device_put with a global
    # sharding would demand process-addressability of every shard (the
    # multi-controller hazard the helpers at the top of this file
    # document) and hang on a survivor submesh after a shrink
    put = lambda a, s: jax.make_array_from_callback(
        a.shape, NamedSharding(mesh, s), lambda idx, a=a: a[idx])
    p = FSDPParams(
        wqkvt=tuple(put(a, s) for a, s in zip(wqkvt, specs.wqkvt)),
        wot=tuple(put(a, s) for a, s in zip(wot, specs.wot)),
        w1t=tuple(put(a, s) for a, s in zip(w1t, specs.w1t)),
        w2t=tuple(put(a, s) for a, s in zip(w2t, specs.w2t)),
    )
    def zeros_like_sharded():
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_callback(
                a.shape, a.sharding,
                lambda idx, sh=a.shape, dt=a.dtype:
                    np.zeros(sh, dt)[idx]), p)

    return ZeroFSDPState(p=p, m=zeros_like_sharded(),
                         v=zeros_like_sharded(),
                         t=jnp.zeros((), jnp.int32))


def attn_from_travel(wqkvt: np.ndarray, wot: np.ndarray, d_model: int,
                     tp: int, dp: int):
    """Invert one layer's attention travel construction on the host:
    ``(wqkvt (tp·q_rows_pad, d), wot (d, d)) -> (wq, wk, wv, wo)`` all
    (d, d) — the EXACT inverse of the :func:`init_zero_fsdp` block
    build (per tp rank: un-pad, un-concat, un-transpose).  This is the
    ONE copy of the inversion math: the publication module's
    host-gather baseline and the fused re-shard program's parity tests
    both call it, so the two paths can never drift
    (``models/publish.py``)."""
    dtp, q_rows, q_rows_pad = _attn_travel_sizes(d_model, tp, dp)
    wq = np.empty((d_model, d_model), wqkvt.dtype)
    wk = np.empty_like(wq)
    wv = np.empty_like(wq)
    for s in range(tp):
        cols = slice(s * dtp, (s + 1) * dtp)
        blk = wqkvt[s * q_rows_pad:s * q_rows_pad + q_rows]  # (3·dtp, d)
        wq[:, cols] = blk[0:dtp].T
        wk[:, cols] = blk[dtp:2 * dtp].T
        wv[:, cols] = blk[2 * dtp:3 * dtp].T
    return wq, wk, wv, np.ascontiguousarray(wot.T)


# ---------------------------------------------------------------------------
# engage policy: commit to the fused datapath only when every per-layer
# kernel plan engages (the mlp/moe discipline)
# ---------------------------------------------------------------------------


def fsdp_engage_reason(d_model: int, d_hidden: int, batch: int,
                       dp: int, tp: int,
                       overlap: Optional[bool] = None,
                       bidirectional: bool = True,
                       wire_dtype=None) -> Optional[str]:
    """None when the layerwise fused datapath would actually run for
    this geometry — BOTH forward agmm gathers (w1, w2; the travelling
    operand is the parameter column shard), both dual mmrs gradient
    reductions AND both fused gathered-wgrad activation-gradient legs
    resolve to the fused kernels (session registers + VMEM plans +
    rung). Otherwise the first decline reason, in the
    ``accl_cmatmul_fallback_total`` vocabulary (``"off"`` is a
    requested baseline, never counted). ``batch`` is the PER-DP-RANK
    row count the step will trace with. Every layer shares one
    geometry, so one resolution covers the stack."""
    from ..ops import collective_matmul as cm

    h_tp = d_hidden // tp
    f32 = jnp.float32
    checks = (
        # forward gathers: trav = (h_tp/dp, d) and (d/dp, h_tp) shards,
        # the matmul operand is the (k, batch) activation panel
        lambda: cm.agmm_engage_reason(
            h_tp // dp, d_model, batch, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, w_dtype=f32),
        lambda: cm.agmm_engage_reason(
            d_model // dp, h_tp, batch, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, w_dtype=f32),
        # gradient reductions: the custom_vjp duals —
        # mmrs(dy (h_tp, b), xᵀᵀ (b, d)) and mmrs(dy (d, b), uᵀ (b, h_tp))
        lambda: cm.mmrs_engage_reason(
            h_tp, batch, d_model, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, w_dtype=f32),
        lambda: cm.mmrs_engage_reason(
            d_model, batch, h_tp, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, w_dtype=f32),
        # activation gradients: the agmm VJPs' dx — the fused
        # gathered-wgrad (trav = the weight shard, loc = dy; resident
        # only, so a dw panel that misses VMEM must decline the WHOLE
        # commit, never run silently unfused inside a "fused" schedule)
        lambda: cm.wgrad_engage_reason(
            h_tp // dp, d_model, batch, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, loc_dtype=f32),
        lambda: cm.wgrad_engage_reason(
            d_model // dp, h_tp, batch, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, loc_dtype=f32),
    )
    for check in checks:
        reason = check()
        if reason is not None:
            return reason
    return None


def fsdp_engages(d_model: int, d_hidden: int, batch: int, dp: int, tp: int,
                 overlap: Optional[bool] = None,
                 bidirectional: bool = True,
                 wire_dtype=None) -> bool:
    """:func:`fsdp_engage_reason` collapsed to a bool (dp == 1 is the
    degenerate single-shard case — nothing to overlap)."""
    return dp > 1 and fsdp_engage_reason(
        d_model, d_hidden, batch, dp, tp, overlap, bidirectional,
        wire_dtype) is None


def fsdp_attn_engage_reason(d_model: int, batch: int, dp: int, tp: int,
                            overlap: Optional[bool] = None,
                            bidirectional: bool = True,
                            wire_dtype=None) -> Optional[str]:
    """None when the ATTENTION leg of the layerwise step rides the agmm
    family too — the Wqkvᵀ and Woᵀ travel shards' forward gathers, dual
    mmrs gradient reductions and fused gathered-wgrad activation
    gradients all resolve.  A non-None verdict does NOT demote the
    whole step: the MLP legs (:func:`fsdp_engage_reason`) keep the
    fused schedule and attention commits honestly to the prefetched
    travel-block gather baseline (the ``_bucket_gather`` discipline on
    the SAME travel-layout shards), the decline counted once under
    ``accl_cmatmul_fallback_total{op="zero_fsdp"}``.  Same vocabulary
    as :func:`fsdp_engage_reason`."""
    from ..ops import collective_matmul as cm

    dtp, _, qrp = _attn_travel_sizes(d_model, tp, dp)
    f32 = jnp.float32
    checks = (
        # forward gathers: trav = (qrp/dp, d) and (d/dp, dtp) shards
        lambda: cm.agmm_engage_reason(
            qrp // dp, d_model, batch, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, w_dtype=f32),
        lambda: cm.agmm_engage_reason(
            d_model // dp, dtp, batch, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, w_dtype=f32),
        # gradient reductions: the custom_vjp duals
        lambda: cm.mmrs_engage_reason(
            qrp, batch, d_model, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, w_dtype=f32),
        lambda: cm.mmrs_engage_reason(
            d_model, batch, dtp, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, w_dtype=f32),
        # activation gradients: the agmm VJPs' fused gathered-wgrad
        lambda: cm.wgrad_engage_reason(
            qrp // dp, d_model, batch, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, loc_dtype=f32),
        lambda: cm.wgrad_engage_reason(
            d_model // dp, dtp, batch, dp, f32, overlap, bidirectional,
            wire_dtype=wire_dtype, loc_dtype=f32),
    )
    for check in checks:
        reason = check()
        if reason is not None:
            return reason
    return None


def fsdp_attn_engages(d_model: int, batch: int, dp: int, tp: int,
                      overlap: Optional[bool] = None,
                      bidirectional: bool = True,
                      wire_dtype=None) -> bool:
    """:func:`fsdp_attn_engage_reason` collapsed to a bool (the bench
    lane's ``attn_fused`` honesty flag)."""
    return dp > 1 and fsdp_attn_engage_reason(
        d_model, batch, dp, tp, overlap, bidirectional,
        wire_dtype) is None


# ---------------------------------------------------------------------------
# the bucket gather: unfused all_gather whose GRADIENT is the bucketized
# wire-staged reduce-scatter (rounded once before the wire, accumulated
# across dp hops in the wire dtype — the mm×rs tolerance class)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _bucket_gather(shard, axis: str, wire_dtype):
    return lax.all_gather(shard, axis, axis=0, tiled=True)


def _bucket_gather_fwd(shard, axis, wire_dtype):
    return _bucket_gather(shard, axis, wire_dtype), None


def _bucket_gather_bwd(axis, wire_dtype, _res, g):
    from ..ops import collective_matmul as cm

    wdt, sr = cm._resolve_wire_codec(wire_dtype, g.dtype)
    gw = cm._wire_cast(g, wdt, stochastic=sr)
    gs = lax.psum_scatter(gw, axis, scatter_dimension=0, tiled=True)
    return (gs.astype(g.dtype),)


_bucket_gather.defvjp(_bucket_gather_fwd, _bucket_gather_bwd)


# ---------------------------------------------------------------------------
# block math (ONE copy shared by the fused and flat schedules — the two
# datapaths must agree on every non-collective op for trajectory parity)
# ---------------------------------------------------------------------------


def _attention(q, k, v):
    """(H, S, dh) scaled-dot-product attention: the flash kernel when
    the sequence fits its 128-block tiling, the identical-math jnp
    online path otherwise (tiny smoke geometries). Both SCHEDULES of a
    given geometry take the same branch, so parity never crosses it."""
    if q.shape[1] % 128 == 0:
        from ..ops import flash
        return flash.flash_attention(q, k, v)
    sc = 1.0 / float(np.sqrt(q.shape[-1]))
    s = jnp.einsum("hqd,hkd->hqk", q, k,
                   preferred_element_type=jnp.float32) * sc
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v,
                      preferred_element_type=jnp.float32)


def _attn_sublayer(x, bucket, d_model: int, tp: int, n_heads: int):
    """x (b, d) + the layer's gathered attention bucket -> x + attn(x).
    Heads are tp-sharded (Megatron): each tp rank runs its n_heads/tp
    heads through flash and the output projection's partial products
    combine with one tp psum."""
    dtp, _ = _attn_sizes(d_model, tp)
    wqkv = bucket[:3 * d_model * dtp].reshape(d_model, 3 * dtp)
    wo = bucket[3 * d_model * dtp:4 * d_model * dtp].reshape(dtp, d_model)
    qkv = jnp.dot(x, wqkv, preferred_element_type=jnp.float32)
    q, k, v = jnp.split(qkv, 3, axis=1)          # (b, dtp) each
    heads_tp = n_heads // tp
    dh = dtp // heads_tp

    def to_heads(t):
        return t.reshape(-1, heads_tp, dh).transpose(1, 0, 2)

    o = _attention(to_heads(q), to_heads(k), to_heads(v))
    o = o.transpose(1, 0, 2).reshape(-1, dtp).astype(jnp.float32)
    a = jnp.dot(o, wo, preferred_element_type=jnp.float32)
    if tp > 1:
        a = lax.psum(a, TP_AXIS)
    return x + a


def _attn_sublayer_t(x, mmqkv, mmo, d_model: int, tp: int, n_heads: int,
                     dp: int = 1):
    """x (b, d) -> x + attn(x) with the two projections supplied by the
    schedule in TRAVEL layout (fused agmm closures over the wqkvt/wot
    shards, or plain dots over gathered travel blocks) — the
    ``_mlp_sublayer`` shape applied to attention.  ``mmqkv`` maps the
    (d, b) activation panel to the (q_rows_pad, b) fused qkvᵀ panel
    (pad rows sliced off here), ``mmo`` maps the (dtp, b) head-output
    panel to the (d, b) projection panel.  Heads stay tp-sharded
    (Megatron) with the one output psum."""
    dtp, _, _ = _attn_travel_sizes(d_model, tp, dp)
    qkvt = mmqkv(x.T)                            # (q_rows_pad, b) f32
    q, k, v = (qkvt[i * dtp:(i + 1) * dtp].T for i in range(3))
    heads_tp = n_heads // tp
    dh = dtp // heads_tp

    def to_heads(t):
        return t.reshape(-1, heads_tp, dh).transpose(1, 0, 2)

    o = _attention(to_heads(q), to_heads(k), to_heads(v))
    o = o.transpose(1, 0, 2).reshape(-1, dtp).astype(jnp.float32)
    at = mmo(o.T)                                # (d, b) f32
    if tp > 1:
        at = lax.psum(at, TP_AXIS)
    return x + at.T


def _mlp_sublayer(x, mm1, mm2, tp: int):
    """x (b, d) -> x + W2(gelu(W1 x)) with the two matmuls supplied by
    the schedule (fused agmm closures or plain dots over gathered
    weights). The activations stay in the transposed panel layout
    between the matmuls — the agmm output feeds the next agmm's matmul
    operand directly, no transposes on the hot path."""
    u = jax.nn.gelu(mm1(x.T))                    # (h_tp, b) f32
    yt = mm2(u)                                  # (d, b) f32
    if tp > 1:
        yt = lax.psum(yt, TP_AXIS)
    return x + yt.T


# ---------------------------------------------------------------------------
# the layerwise fused train step (and its committed flat-ravel fallback)
# ---------------------------------------------------------------------------


def build_zero_fsdp_train_step(mesh, n_layers: int, d_model: int,
                               d_hidden: int, n_heads: int,
                               lr: float = 1e-2, b1: float = 0.9,
                               b2: float = 0.999, eps: float = 1e-8,
                               overlap: Optional[bool] = None,
                               prefetch: Optional[bool] = None,
                               wire_dtype=None,
                               bidirectional: bool = True):
    """``step(state, x, y) -> (state, loss)`` — one jitted layerwise
    ZeRO/FSDP train step over the (dp, tp) mesh.

    ``x``/``y``: (B, d_model) global, rows sharded over dp (replicated
    over tp). ``overlap=None`` follows ``ACCLConfig.zero_overlap`` plus
    the cmatmul session registers; True forces the fused kernels, False
    pins the flat-ravel baseline schedule. ``prefetch=None`` follows
    ``ACCLConfig.zero_prefetch``. ``wire_dtype`` stages the fused legs'
    ring payloads AND the bucketized attention-gradient leg compressed
    (None: session ``ACCLConfig.cmatmul_wire_dtype``; "off": full
    precision) — the flat baseline always runs full precision.

    The commit decision is honest, counted and TIERED: the fused
    datapath runs only when :func:`fsdp_engage_reason` resolves None at
    the traced batch shape — and within it, attention rides the agmm
    family too only when :func:`fsdp_attn_engage_reason` also resolves
    (zero unfused collectives in the whole step); a declined attention
    plan commits to the prefetched travel-block gather baseline for
    attention alone (MLP stays fused), counted once. Anything less
    than the MLP commit runs the flat schedule unchanged and the
    decline lands in ``accl_cmatmul_fallback_total{op="zero_fsdp"}``
    (an explicit/session overlap-off is a requested baseline — never
    counted)."""
    dp, tp = mesh.shape[DP_AXIS], mesh.shape[TP_AXIS]
    _validate_geometry(dp, tp, d_model, d_hidden, n_heads)
    axes = tuple(mesh.axis_names)
    L = n_layers
    dtp, q_rows, q_rows_pad = _attn_travel_sizes(d_model, tp, dp)
    h_tp = d_hidden // tp
    lq, lo = (q_rows_pad // dp) * d_model, (d_model // dp) * dtp
    l1, l2 = (h_tp // dp) * d_model, (d_model // dp) * h_tp
    per = lq + lo + l1 + l2

    def _resolved_overlap():
        if overlap is None:
            return None if _OVERLAP_DEFAULT else False
        return overlap

    def _fused_loss(p: FSDPParams, x, y, do_prefetch: bool, ov,
                    attn_fused: bool):
        from ..ops import collective_matmul as cm

        def agmm(trav, panel):
            return cm.all_gather_matmul(trav, panel, DP_AXIS, axes, ov,
                                        bidirectional, wire_dtype)

        def mlp(h, l):
            return _mlp_sublayer(
                h,
                lambda xt, l=l: agmm(p.w1t[l], xt),
                lambda u, l=l: agmm(p.w2t[l], u),
                tp)

        if attn_fused:
            # attention-on-agmm: the Wqkv/Wo travel shards ride the
            # SAME fused gather×matmul as the MLP — the step contains
            # no unfused parameter collective at all, so there is no
            # bucket to prefetch
            h = x
            for l in range(L):
                h = _attn_sublayer_t(
                    h,
                    lambda xt, l=l: agmm(p.wqkvt[l], xt),
                    lambda ot, l=l: agmm(p.wot[l], ot),
                    d_model, tp, n_heads, dp)
                h = mlp(h, l)
            return jnp.mean((h - y) ** 2)

        # attention plan declined: the travel blocks gather per layer
        # with cross-layer prefetch (the bucket baseline on the same
        # shards — gradient bucketized + wire-staged), MLP stays fused
        def gather(l, tie=None):
            def shard(a):
                if tie is None:
                    return a
                # prefetch declined: tie the gather's operand to the
                # previous layer's output (a zero-valued scalar
                # dependency — this jax's optimization_barrier has no
                # AD rule) so the collective cannot be hoisted above
                # the layer boundary
                return a + (tie[0, 0] * 0.0).astype(a.dtype)
            return (_bucket_gather(shard(p.wqkvt[l]), DP_AXIS, wire_dtype),
                    _bucket_gather(shard(p.wot[l]), DP_AXIS, wire_dtype))

        h = x
        nxt = gather(0)
        for l in range(L):
            wq_f, wo_f = nxt
            if l + 1 < L and do_prefetch:
                # cross-layer prefetch: layer l+1's travel-block gather
                # is issued BEFORE layer l's compute — independent of
                # h, so the collective overlaps flash + the fused
                # matmuls (double-buffered: at most two gathered layers
                # live)
                nxt = gather(l + 1)
            h = _attn_sublayer_t(
                h,
                lambda xt, w=wq_f: jnp.dot(
                    w, xt, preferred_element_type=jnp.float32),
                lambda ot, w=wo_f: jnp.dot(
                    w, ot, preferred_element_type=jnp.float32),
                d_model, tp, n_heads, dp)
            h = mlp(h, l)
            if l + 1 < L and not do_prefetch:
                nxt = gather(l + 1, tie=h)
        return jnp.mean((h - y) ** 2)

    def _flat_step_grads(p: FSDPParams, x, y):
        """The flat-ravel schedule: ONE monolithic all_gather of every
        layer's shards, compute with fully materialized weights, ONE
        monolithic psum_scatter of the raveled gradient — the baseline
        the fused step's overlap efficiency is measured against. Same
        block math as the fused schedules (``_attn_sublayer_t`` over
        the gathered travel blocks), so the two datapaths agree on
        every non-collective op."""
        flat = jnp.concatenate(
            [seg for l in range(L)
             for seg in (p.wqkvt[l].ravel(), p.wot[l].ravel(),
                         p.w1t[l].ravel(), p.w2t[l].ravel())])
        full = lax.all_gather(flat, DP_AXIS, axis=0,
                              tiled=True).reshape(dp, L * per)
        wqf, wof, w1f, w2f = [], [], [], []
        for l in range(L):
            off = l * per
            wqf.append(full[:, off:off + lq]
                       .reshape(dp, q_rows_pad // dp, d_model)
                       .reshape(q_rows_pad, d_model))
            wof.append(full[:, off + lq:off + lq + lo]
                       .reshape(dp, d_model // dp, dtp)
                       .reshape(d_model, dtp))
            w1f.append(full[:, off + lq + lo:off + lq + lo + l1]
                       .reshape(dp, h_tp // dp, d_model)
                       .reshape(h_tp, d_model))
            w2f.append(full[:, off + lq + lo + l1:off + per]
                       .reshape(dp, d_model // dp, h_tp)
                       .reshape(d_model, h_tp))

        def loss_fn(fulls):
            wql, wol, w1l, w2l = fulls
            h = x
            for l in range(L):
                h = _attn_sublayer_t(
                    h,
                    lambda xt, l=l: jnp.dot(
                        wql[l], xt, preferred_element_type=jnp.float32),
                    lambda ot, l=l: jnp.dot(
                        wol[l], ot, preferred_element_type=jnp.float32),
                    d_model, tp, n_heads, dp)
                h = _mlp_sublayer(
                    h,
                    lambda xt, l=l: jnp.dot(
                        w1l[l], xt, preferred_element_type=jnp.float32),
                    lambda u, l=l: jnp.dot(
                        w2l[l], u, preferred_element_type=jnp.float32),
                    tp)
            return jnp.mean((h - y) ** 2)

        loss, (gq, go, g1, g2) = jax.value_and_grad(loss_fn)(
            (tuple(wqf), tuple(wof), tuple(w1f), tuple(w2f)))
        segs = []
        for l in range(L):
            segs.append(gq[l].reshape(dp, q_rows_pad // dp, d_model)
                        .reshape(dp, lq))
            segs.append(go[l].reshape(dp, d_model // dp, dtp)
                        .reshape(dp, lo))
            segs.append(g1[l].reshape(dp, h_tp // dp, d_model)
                        .reshape(dp, l1))
            segs.append(g2[l].reshape(dp, d_model // dp, h_tp)
                        .reshape(dp, l2))
        flatg = jnp.concatenate(segs, axis=1).reshape(-1)
        gsh = lax.psum_scatter(flatg, DP_AXIS, scatter_dimension=0,
                               tiled=True)
        gwqt, gwot, gw1t, gw2t = [], [], [], []
        for l in range(L):
            off = l * per
            gwqt.append(gsh[off:off + lq]
                        .reshape(q_rows_pad // dp, d_model))
            gwot.append(gsh[off + lq:off + lq + lo]
                        .reshape(d_model // dp, dtp))
            gw1t.append(gsh[off + lq + lo:off + lq + lo + l1]
                        .reshape(h_tp // dp, d_model))
            gw2t.append(gsh[off + lq + lo + l1:off + per]
                        .reshape(d_model // dp, h_tp))
        return loss, FSDPParams(tuple(gwqt), tuple(gwot),
                                tuple(gw1t), tuple(gw2t))

    def local_step(state: ZeroFSDPState, x, y):
        p, m, v, t = state
        b = x.shape[0]
        ov = _resolved_overlap()
        reason = None
        attn_reason = None
        if dp > 1:
            reason = fsdp_engage_reason(d_model, d_hidden, b, dp, tp, ov,
                                        bidirectional, wire_dtype)
            attn_reason = fsdp_attn_engage_reason(d_model, b, dp, tp, ov,
                                                  bidirectional, wire_dtype)
        fused = dp > 1 and reason is None
        attn_fused = fused and attn_reason is None
        if fused:
            if not attn_fused and attn_reason != "off":
                # attention alone declined the agmm commit: the step
                # stays fused for the MLP legs but attention runs the
                # prefetched-gather baseline — counted once, honestly
                from ..ops.collective_matmul import _note_fallback
                _note_fallback(FSDP_OP, attn_reason)
            do_prefetch = (_PREFETCH_DEFAULT if prefetch is None
                           else bool(prefetch))
            if not attn_fused and L > 1:
                _metrics.note_zero_prefetch(
                    "hit" if do_prefetch else "decline", L - 1)
            loss, grads = jax.value_and_grad(
                _fused_loss, argnums=0)(p, x, y, do_prefetch, ov,
                                        attn_fused)
        else:
            if dp > 1 and reason != "off":
                from ..ops.collective_matmul import _note_fallback
                _note_fallback(FSDP_OP, reason)
            loss, grads = _flat_step_grads(p, x, y)
        # the collectives above deliver Σ_r (each rank's local-loss
        # contribution); the training objective is the GLOBAL batch mean
        grads = jax.tree_util.tree_map(lambda g: g / dp, grads)
        t_new = t + 1
        tf = t_new.astype(jnp.float32)

        def adam(pw, mw, vw, gw):
            m_new = b1 * mw + (1 - b1) * gw
            v_new = b2 * vw + (1 - b2) * gw * gw
            mhat = m_new / (1 - b1 ** tf)
            vhat = v_new / (1 - b2 ** tf)
            return pw - lr * mhat / (jnp.sqrt(vhat) + eps), m_new, v_new

        new_p, new_m, new_v = [], [], []
        flat_p, treedef = jax.tree_util.tree_flatten(p)
        flat_m = jax.tree_util.tree_leaves(m)
        flat_v = jax.tree_util.tree_leaves(v)
        flat_g = jax.tree_util.tree_leaves(grads)
        for pw, mw, vw, gw in zip(flat_p, flat_m, flat_v, flat_g):
            a, bm, bv = adam(pw, mw, vw, gw)
            new_p.append(a)
            new_m.append(bm)
            new_v.append(bv)
        unflat = lambda leaves: jax.tree_util.tree_unflatten(treedef, leaves)
        loss = lax.psum(loss, DP_AXIS) / dp
        return (ZeroFSDPState(unflat(new_p), unflat(new_m),
                              unflat(new_v), t_new), loss)

    from jax import shard_map
    specs = fsdp_param_specs(L)
    state_specs = ZeroFSDPState(p=specs, m=specs, v=specs, t=P())
    return jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(state_specs, P(DP_AXIS, None), P(DP_AXIS, None)),
        out_specs=((state_specs, P())),
        check_vma=False,
    ))
