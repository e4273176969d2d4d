"""Context (sequence) parallelism: ring attention + all-to-all (Ulysses)
resharding built on the framework's collectives.

The reference is the substrate below model parallelism (SURVEY.md §2.6 —
no attention code exists in it); its scalable-payload machinery is
segmentation + pipelining (§5), which it points at as "the building block
that ring-attention/context-parallel layers would consume". These are
those layers, TPU-first:

* **Ring attention** (`build_ring_attention`): Q/K/V sharded over the
  sequence axis, one block per rank. P steps of blockwise attention with
  online-softmax accumulation; K/V blocks rotate one hop per step via
  ``ppermute`` — the same neighbor-only ring schedule as the ring
  collectives (fw segmented allreduce ``ccl_offload_control.c:1888-2071``),
  so sequence length scales with the mesh while every hop stays on an ICI
  link. Compute (two matmuls per step, MXU-bound) overlaps the next hop's
  transfer under XLA's scheduler; with ``causal=True`` fully-masked future
  blocks skip both matmuls (≈half the FLOPs as the mesh grows).
* **Ulysses attention** (`build_ulysses_attention`): sequence-sharded
  Q/K/V are re-sharded to head-sharded/full-sequence via ONE fused
  ``lax.all_to_all`` (q/k/v stacked), attention runs locally per head
  group — blockwise, never materializing the (S, S) score matrix — and a
  second all-to-all restores sequence sharding. Two collectives total —
  the all-to-all sequence-parallel alternative when heads ≥ world.

Numerics: softmax state (running max, normalizer, accumulator) is carried
in float32 regardless of input dtype (standard flash-attention practice);
outputs cast back to the input dtype. Both strategies are deterministic
(fixed ring order / fixed reshard) and compose with the rest of the
framework: inputs are the communicator's (world, ...) sharded arrays,
programs are cached jitted shard_map programs like every collective here.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..communicator import Communicator
from .primitives import AXIS, _smap
from .ring import _fwd_perm

_F32 = jnp.float32


def _online_block(q, kb, vb, acc, m, l, qpos, kpos, causal: bool,
                  scale: float):
    """One blockwise-attention accumulation step (online softmax).

    q: (n, d); kb/vb: (nb, d); acc: (n, d) f32; m/l: (n,) f32. Returns
    updated (acc, m, l). Deterministic: the caller fixes the block order.
    Scores and state are f32; only the two matmuls run in the input dtype
    with f32 accumulation (MXU-native mixed precision)."""
    scores = jnp.matmul(q, kb.T, preferred_element_type=_F32) * scale
    if causal:
        mask = qpos[:, None] >= kpos[None, :]
        scores = jnp.where(mask, scores, -jnp.inf)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # exp(-inf - -inf) guards: a fully-masked row keeps m=-inf, p=0
    p = jnp.exp(scores - m_new[:, None])
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_new, -jnp.inf))
    alpha = jnp.where(jnp.isfinite(m), alpha, 0.0)
    l_new = l * alpha + p.sum(axis=-1)
    pv = jnp.matmul(p.astype(vb.dtype), vb, preferred_element_type=_F32)
    acc_new = acc * alpha[:, None] + pv
    return acc_new, m_new, l_new


def _merge_partials(o_c, lse_c, o_s, lse_s):
    """Merge two normalized partial attentions by their log-sum-exps:
    out = (w_c·o_c + w_s·o_s)/(w_c + w_s), w = exp(lse − max). A fully
    masked partial carries lse = −1e30, so its weight is an exact zero."""
    m = jnp.maximum(lse_c, lse_s)
    wc = jnp.exp(lse_c - m)
    ws = jnp.exp(lse_s - m)
    tot = wc + ws
    safe = jnp.where(tot > 0, tot, 1.0)
    o = (o_c * wc[:, None] + o_s * ws[:, None]) / safe[:, None]
    return o, m + jnp.log(safe)


def _after_step_kernels(kv, lse):
    """Make the K/V hop wait for this ring step's flash kernels, by a
    data dependency on their running ``lse`` (a pytree of (n,) arrays).

    For the interpret rung only. There each interpreted Pallas kernel
    parks a host thread in a barrier across devices, and XLA CPU runs a
    ``ppermute`` beside it on a thread of the same pool (one thread per
    device), since nothing orders the two. With two parked threads per
    device the pool runs dry with one rank short of the permute, and
    XLA aborts the run at its collective timeout. ``lse[0] * 0`` is an
    exact zero the compiler cannot fold away (NaN * 0 is not 0), so
    adding it chains each hop after the step's kernels, and by the
    transpose each backward hop before the previous step's kernels. On
    TPU the hop should overlap the step's compute, so it is not used
    there."""
    dep = sum(l[0] for l in jax.tree.leaves(lse)) * 0
    return tuple(x + dep.astype(x.dtype) for x in kv)


def build_ring_attention(comm: Communicator, causal: bool = False,
                         scale: Optional[float] = None,
                         use_flash: bool = False) -> Callable:
    """Ring attention over the communicator's mesh.

    Inputs: q, k, v of global shape (world, n, d) — rank r owns sequence
    block [r*n, (r+1)*n). Output: (world, n, d), the exact softmax
    attention of the full (world*n)-long sequence, accumulated online so
    no rank ever materializes more than one remote K/V block.

    ``use_flash`` runs EACH ring step through the fused Pallas flash
    kernel (:func:`accl_tpu.ops.flash.flash_attention_lse`): the step's
    (out, lse) pair merges into the running result by log-sum-exp
    weighting — same math as the online-softmax carry, at kernel speed.
    Requires the per-rank block n to be a multiple of the 128-wide flash
    blocks; any head dim (64/96/...) works via the kernel's lane padding.
    Differentiable end-to-end (the lse cotangent folds into the flash
    backward).
    """
    world = comm.world_size
    perm = _fwd_perm(world)

    if use_flash:
        import jax as _jax
        from ..ops import flash as _flash
        # lax.cond around interpret-mode pallas is pathologically slow on
        # the CPU rung; there the fully-masked steps are dropped by exact
        # lse weighting instead (weight = exp(-1e30 - m) = 0). On real TPU
        # the cond skips the kernel entirely — the reference's
        # masked-block skip at zero FLOPs.
        skip_via_cond = _jax.default_backend() == "tpu"
        interpreted = _flash._interpret_params() is not None

        def body_flash(q, k, v):
            q, k, v = q[0], k[0], v[0]                # (n, d) local blocks
            n, d = q.shape
            sc = scale if scale is not None else 1.0 / (d ** 0.5)
            rank = lax.axis_index(AXIS)
            o_c = jnp.zeros((n, d), _F32)
            lse_c = jnp.full((n,), -1e30, _F32)
            kb, vb = k, v
            for s in range(world):
                src = jnp.mod(rank - s, world)
                if s == 0:
                    # the diagonal block: local causal mask is the global
                    # one (both sides share the rank*n offset)
                    o_s, lse_s = _flash.flash_attention_lse(
                        q, kb, vb, causal=causal, scale=sc)
                    o_c, lse_c = _merge_partials(
                        o_c, lse_c, o_s.astype(_F32), lse_s)
                else:
                    def attend(carry, kb=kb, vb=vb):
                        o_s, lse_s = _flash.flash_attention_lse(
                            q, kb, vb, causal=False, scale=sc)
                        return _merge_partials(
                            carry[0], carry[1], o_s.astype(_F32), lse_s)

                    if causal and skip_via_cond:
                        # future blocks (src > rank) are fully masked: skip
                        # both matmuls entirely
                        o_c, lse_c = lax.cond(
                            src <= rank, attend, lambda c: c, (o_c, lse_c))
                    elif causal:
                        o_s, lse_s = _flash.flash_attention_lse(
                            q, kb, vb, causal=False, scale=sc)
                        lse_s = jnp.where(src <= rank, lse_s, -1e30)
                        o_c, lse_c = _merge_partials(
                            o_c, lse_c, o_s.astype(_F32), lse_s)
                    else:
                        o_c, lse_c = attend((o_c, lse_c))
                if s + 1 < world:
                    if interpreted:
                        kb, vb = _after_step_kernels((kb, vb), lse_c)
                    kb = lax.ppermute(kb, AXIS, perm)
                    vb = lax.ppermute(vb, AXIS, perm)
            return o_c.astype(q.dtype)[None]

        return _smap(comm, body_flash, 3)

    def body(q, k, v):
        q, k, v = q[0], k[0], v[0]                    # (n, d) local blocks
        n, d = q.shape
        sc = scale if scale is not None else 1.0 / (d ** 0.5)
        rank = lax.axis_index(AXIS)
        qpos = rank * n + jnp.arange(n)
        acc = jnp.zeros((n, d), _F32)
        m = jnp.full((n,), -jnp.inf, _F32)
        l = jnp.zeros((n,), _F32)
        kb, vb = k, v
        for s in range(world):
            # after s forward hops, this rank holds block (rank - s) % P
            src = jnp.mod(rank - s, world)
            kpos = src * n + jnp.arange(n)

            def attend(carry, kb=kb, vb=vb, kpos=kpos):
                a, mm, ll = carry
                return _online_block(q, kb, vb, a, mm, ll, qpos, kpos,
                                     causal, sc)

            if causal:
                # a future block (src > rank) is fully masked: skip both
                # matmuls entirely — the rotation below still runs
                acc, m, l = lax.cond(src <= rank, attend,
                                     lambda c: c, (acc, m, l))
            else:
                acc, m, l = attend((acc, m, l))
            if s + 1 < world:
                # rotate K/V one hop; XLA overlaps this with the next
                # step's matmuls where the schedule allows
                kb = lax.ppermute(kb, AXIS, perm)
                vb = lax.ppermute(vb, AXIS, perm)
        safe_l = jnp.where(l > 0, l, 1.0)
        return (acc / safe_l[:, None]).astype(q.dtype)[None]

    return _smap(comm, body, 3)


def zigzag_layout(x, world: int):
    """Permute a (S, ...) sequence-major array into the zigzag ring
    layout: rank r owns half-blocks ``r`` and ``2W-1-r`` of the 2W
    half-blocks — returns (world, S//world, ...)."""
    S = x.shape[0]
    h = S // (2 * world)
    halves = x.reshape(2 * world, h, *x.shape[1:])
    idx = np.stack([np.arange(world), 2 * world - 1 - np.arange(world)], 1)
    return halves[idx.reshape(-1)].reshape(world, 2 * h, *x.shape[1:])


def zigzag_unlayout(x, world: int):
    """Inverse of :func:`zigzag_layout`: (world, n, ...) -> (S, ...)."""
    n = x.shape[1]
    h = n // 2
    halves = x.reshape(2 * world, h, *x.shape[2:])
    idx = np.stack([np.arange(world), 2 * world - 1 - np.arange(world)], 1)
    inv = np.argsort(idx.reshape(-1))
    return halves[inv].reshape(2 * world * h, *x.shape[2:])


def build_zigzag_ring_attention(comm: Communicator,
                                scale: Optional[float] = None,
                                use_flash: bool = False) -> Callable:
    """Load-balanced CAUSAL ring attention (zigzag block order).

    Plain causal ring attention is imbalanced: rank r has r+1 live steps
    of W, so rank 0 idles ~half the wall-clock while rank W-1 computes
    every step (~50% utilization at scale). Zigzag assigns each rank two
    HALF-blocks — indices r and 2W-1-r of the 2W half-blocks (use
    :func:`zigzag_layout`) — which makes every ring step cost two
    quarter-block attentions on every rank (step 0 runs a third,
    half-masked diagonal block — one extra quarter total per rank, the
    same on every rank):

    * the late half (index 2W-1-r ≥ W) attends EVERY arriving early half
      in full;
    * plus exactly one of {early-vs-early (src ≤ r), late-vs-late
      (src ≥ r)} — the two branches are the same shape, so the ``cond``
      is load-neutral; positional masking inside the block keeps the
      diagonal exact.

    Inputs/outputs are (world, n, d) in the zigzag layout; masking uses
    global positions, so the result equals dense causal attention on the
    un-permuted sequence (see ``zigzag_unlayout``). K/V rotate one hop a
    step like the plain ring — the same neighbor-only ICI schedule.

    ``use_flash``: the zigzag schedule is exactly flash-shaped — every
    half-block pair is either a FULL attention (cross-half, strictly
    earlier positions) or an ALIGNED diagonal (own half at step 0), so
    each pair runs through the fused kernel
    (:func:`accl_tpu.ops.flash.flash_attention_lse`, ``causal=False`` /
    ``causal=True`` respectively) and merges by log-sum-exp weighting; no
    arbitrary positional mask is ever needed. Requires the per-rank HALF
    block (n/2) to be a multiple of the 128-wide flash blocks.
    """
    world = comm.world_size
    perm = _fwd_perm(world)

    if use_flash:
        import jax as _jax
        from ..ops import flash as _flash
        # same interpret-mode caveat as build_ring_attention: lax.cond
        # around interpret-mode pallas is pathologically slow to build on
        # the CPU rung, so there both branches run and lse masking picks
        # one; on real TPU the cond skips the dead branch's kernel
        skip_via_cond = _jax.default_backend() == "tpu"
        interpreted = _flash._interpret_params() is not None

        def body_flash(q, k, v):
            q, k, v = q[0], k[0], v[0]
            n, d = q.shape
            if n % 2:
                raise ValueError(
                    f"zigzag needs an even per-rank block, got {n}")
            h = n // 2
            sc = scale if scale is not None else 1.0 / (d ** 0.5)
            rank = lax.axis_index(AXIS)
            qA, qB = q[:h], q[h:]
            oA = jnp.zeros((h, d), _F32)
            lA = jnp.full((h,), -1e30, _F32)
            oB = jnp.zeros((h, d), _F32)
            lB = jnp.full((h,), -1e30, _F32)
            kb, vb = k, v
            for s in range(world):
                src = jnp.mod(rank - s, world)
                kvA = (kb[:h], vb[:h])
                kvB = (kb[h:], vb[h:])

                # pair 1: late q-half vs arriving early kv-half — always
                # strictly earlier positions, a full attend
                o_s, l_s = _flash.flash_attention_lse(
                    qB, kvA[0], kvA[1], causal=False, scale=sc)
                oB, lB = _merge_partials(oB, lB, o_s.astype(_F32), l_s)

                if s == 0:
                    # own kv: both diagonals are ALIGNED causal blocks
                    o_s, l_s = _flash.flash_attention_lse(
                        qA, kvA[0], kvA[1], causal=True, scale=sc)
                    oA, lA = _merge_partials(oA, lA, o_s.astype(_F32), l_s)
                    o_s, l_s = _flash.flash_attention_lse(
                        qB, kvB[0], kvB[1], causal=True, scale=sc)
                    oB, lB = _merge_partials(oB, lB, o_s.astype(_F32), l_s)
                else:
                    # equal-shape full attends: early-vs-early when the
                    # arriving block is older (src < rank, strictly
                    # earlier positions), late-vs-late otherwise
                    take_early = src <= rank

                    def early(st, kvA=kvA):
                        o_s, l_s = _flash.flash_attention_lse(
                            qA, kvA[0], kvA[1], causal=False, scale=sc)
                        (a, la), b = st
                        return (_merge_partials(
                            a, la, o_s.astype(_F32), l_s), b)

                    def late(st, kvB=kvB):
                        o_s, l_s = _flash.flash_attention_lse(
                            qB, kvB[0], kvB[1], causal=False, scale=sc)
                        a, (b, lb) = st
                        return (a, _merge_partials(
                            b, lb, o_s.astype(_F32), l_s))

                    if skip_via_cond:
                        (oA, lA), (oB, lB) = lax.cond(
                            take_early, early, late, ((oA, lA), (oB, lB)))
                    else:
                        (oA2, lA2), _ = early(((oA, lA), (oB, lB)))
                        _, (oB2, lB2) = late(((oA, lA), (oB, lB)))
                        oA = jnp.where(take_early, oA2, oA)
                        lA = jnp.where(take_early, lA2, lA)
                        oB = jnp.where(take_early, oB, oB2)
                        lB = jnp.where(take_early, lB, lB2)
                if s + 1 < world:
                    if interpreted:
                        kb, vb = _after_step_kernels((kb, vb), (lA, lB))
                    kb = lax.ppermute(kb, AXIS, perm)
                    vb = lax.ppermute(vb, AXIS, perm)
            return jnp.concatenate([oA, oB], 0).astype(q.dtype)[None]

        return _smap(comm, body_flash, 3)

    def body(q, k, v):
        q, k, v = q[0], k[0], v[0]                    # (n, d): two halves
        n, d = q.shape
        if n % 2:
            raise ValueError(f"zigzag needs an even per-rank block, got {n}")
        h = n // 2
        sc = scale if scale is not None else 1.0 / (d ** 0.5)
        rank = lax.axis_index(AXIS)
        iA = rank                                      # early half index
        iB = 2 * world - 1 - rank                      # late half index
        posA = iA * h + jnp.arange(h)
        posB = iB * h + jnp.arange(h)
        qA, qB = q[:h], q[h:]
        stA = (jnp.zeros((h, d), _F32), jnp.full((h,), -jnp.inf, _F32),
               jnp.zeros((h,), _F32))
        stB = (jnp.zeros((h, d), _F32), jnp.full((h,), -jnp.inf, _F32),
               jnp.zeros((h,), _F32))
        kb, vb = k, v
        for s in range(world):
            src = jnp.mod(rank - s, world)
            jA = src                                   # arriving early half
            jB = 2 * world - 1 - src                   # arriving late half
            kposA = jA * h + jnp.arange(h)
            kposB = jB * h + jnp.arange(h)
            kvA = (kb[:h], vb[:h])
            kvB = (kb[h:], vb[h:])

            # pair 1: late q-half vs arriving early kv-half — ALWAYS a
            # full attend (iB >= W > jA), masking is a no-op but kept for
            # the s=0 case where jA == src == rank < iB still holds
            stB = _online_block(qB, kvA[0], kvA[1], *stB, posB, kposA,
                                True, sc)

            # pair 2: equal-shape branches — early-vs-early when the
            # arriving block is not newer (src <= r), late-vs-late
            # otherwise; positional masks make the diagonals exact
            def early(st, kvA=kvA, kposA=kposA):
                a = _online_block(qA, kvA[0], kvA[1], *st[0], posA,
                                  kposA, True, sc)
                return a, st[1]

            def late(st, kvB=kvB, kposB=kposB):
                b = _online_block(qB, kvB[0], kvB[1], *st[1], posB,
                                  kposB, True, sc)
                return st[0], b

            stA, stB = lax.cond(src <= rank, early, late, (stA, stB))
            if s == 0:
                # the diagonal late-vs-late block (own kv): src == rank
                # routed to `early` above, so do B/B here
                stB = _online_block(qB, kvB[0], kvB[1], *stB, posB, kposB,
                                    True, sc)
            if s + 1 < world:
                kb = lax.ppermute(kb, AXIS, perm)
                vb = lax.ppermute(vb, AXIS, perm)

        def norm(st):
            acc, m, l = st
            safe = jnp.where(l > 0, l, 1.0)
            return acc / safe[:, None]

        return jnp.concatenate([norm(stA), norm(stB)], 0).astype(
            q.dtype)[None]

    return _smap(comm, body, 3)


def build_ulysses_attention(comm: Communicator, n_heads: int,
                            causal: bool = False,
                            scale: Optional[float] = None,
                            use_flash: bool = False) -> Callable:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

    Inputs: q, k, v of global shape (world, n, n_heads, d) — sequence
    sharded. One fused ``lax.all_to_all`` over the stacked q/k/v re-shards
    to (n_heads/world) heads × full sequence per rank, attention runs
    locally (blockwise online softmax — O(S·n) memory, never the (S, S)
    score matrix), and the inverse all-to-all restores sequence sharding.
    ``n_heads`` must be divisible by the world size.

    ``use_flash`` runs the local attention through the fused Pallas flash
    kernel (:mod:`accl_tpu.ops.flash`, forward AND backward kernels) —
    requires the global sequence to be a multiple of its 128-wide blocks
    and ``d % 128 == 0``; shape violations raise at first trace.
    """
    world = comm.world_size
    if n_heads % world != 0:
        raise ValueError(f"n_heads {n_heads} not divisible by world {world}")

    # one online-softmax step vectorized over the local head group
    _vblock = jax.vmap(_online_block,
                       in_axes=(0, 0, 0, 0, 0, 0, None, None, None, None))

    def local_attn(q, k, v, n, sc):
        # q/k/v: (h, S, d) — full sequence, this rank's head group.
        # Blockwise over n-sized chunks: memory O(h·S·n), not O(h·S²).
        h, S, d = q.shape
        qpos = jnp.arange(S)
        acc = jnp.zeros((h, S, d), _F32)
        m = jnp.full((h, S), -jnp.inf, _F32)
        l = jnp.zeros((h, S), _F32)
        for b in range(S // n):
            kb = k[:, b * n:(b + 1) * n]
            vb = v[:, b * n:(b + 1) * n]
            kpos = b * n + jnp.arange(n)
            acc, m, l = _vblock(q, kb, vb, acc, m, l, qpos, kpos, causal, sc)
        safe_l = jnp.where(l > 0, l, 1.0)
        return (acc / safe_l[..., None]).astype(q.dtype)

    def body(q, k, v):
        n, H, d = q.shape[1:]
        if H != n_heads:
            raise ValueError(
                f"input head axis {H} != declared n_heads {n_heads}")
        sc = scale if scale is not None else 1.0 / (d ** 0.5)
        # ONE fused reshard for q/k/v: stack, scatter head groups, gather
        # every rank's sequence block (in rank order, so the concat IS the
        # global sequence)
        qkv = jnp.stack([q[0], k[0], v[0]])           # (3, n, H, d)
        qkv = lax.all_to_all(qkv, AXIS, split_axis=2, concat_axis=1,
                             tiled=True)              # (3, world*n, h, d)
        qh, kh, vh = (jnp.moveaxis(a, 1, 0) for a in qkv)  # (h, S, d) each
        if use_flash:
            from ..ops import flash
            out = flash.flash_attention(qh, kh, vh, causal=causal, scale=sc)
        else:
            out = local_attn(qh, kh, vh, n, sc)       # (h, S, d)
        # inverse: scatter sequence blocks back to their owners, gather
        # every head group (in rank order = global head order)
        back = lax.all_to_all(out, AXIS, split_axis=1, concat_axis=0,
                              tiled=True)             # (H, n, d)
        return jnp.moveaxis(back, 0, 1)[None]         # (1, n, H, d)

    return _smap(comm, body, 3)
