"""Flagship example: tensor+data-parallel MLP trained on the framework.

The reference is a communication library; its "models" are the collectives.
This module is the demonstration a framework user needs: a Megatron-style
MLP block whose forward, backward and optimizer run as **one** jitted
shard_map program over a 2-D (dp, tp) mesh, with every collective issued
device-side through :mod:`accl_tpu.device_api` — the scaled-up version of
the vadd_put pattern (compute fused with collectives, host only launches).

Sharding (Megatron column/row parallel):
  W1 (d, h): columns sharded over tp -> local (d, h/tp)
  W2 (h, d): rows    sharded over tp -> local (h/tp, d)
  activations never materialize h; the partial products combine over tp.
  Batch sharded over dp; gradients dp-averaged with a psum (the classic
  DP gradient allreduce, here fused into the step program).

Two selectable TP datapaths (``overlap``; the A/B the collective-matmul
kernels are benched against):

* **psum baseline** (``overlap=False``): the textbook sequential
  pattern — local matmuls, then a blocking ``psum`` combine; ICI idles
  during MXU time and vice versa;
* **overlapped** (``overlap=True``): the forward column-parallel matmul
  runs as :func:`device_api.all_gather_matmul` over the batch rows'
  tp-shards and the row-parallel combine as
  :func:`device_api.matmul_reduce_scatter` — each ring hop's transfer
  flies while the MXU computes the previous hop's block
  (``ops/collective_matmul.py``), in the backward too (the kernels are
  ``custom_vjp`` duals of each other). Same math: the loss trajectory
  matches the baseline to float tolerance.

``overlap=None`` (default) follows the session config
(``ACCLConfig.cmatmul_overlap`` write-through); the per-call argument on
:func:`make_forward` / :func:`make_train_step` pins either path. The
block-geometry policy inside the kernels still falls back to the unfused
pair when the staged shard misses the scoped-VMEM budget, and the
baseline is used when the per-dp-rank batch does not divide by tp.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from .. import device_api as dapi

DP_AXIS = "dp"
TP_AXIS = "tp"


class MLPParams(NamedTuple):
    w1: jax.Array  # (d, h/tp) local
    b1: jax.Array  # (h/tp,)   local
    w2: jax.Array  # (h/tp, d) local
    b2: jax.Array  # (d,)      replicated


def init_params(key, d_model: int, d_hidden: int) -> MLPParams:
    k1, k2 = jax.random.split(key)
    scale1 = (2.0 / d_model) ** 0.5
    scale2 = (2.0 / d_hidden) ** 0.5
    return MLPParams(
        w1=jax.random.normal(k1, (d_model, d_hidden), jnp.float32) * scale1,
        b1=jnp.zeros((d_hidden,), jnp.float32),
        w2=jax.random.normal(k2, (d_hidden, d_model), jnp.float32) * scale2,
        b2=jnp.zeros((d_model,), jnp.float32),
    )


def apply(p: MLPParams, x):
    """Dense single-device MLP forward (gelu between the two matmuls,
    f32 accumulation) — ONE copy of the block math shared by the ZeRO
    flat-ravel demo (``models/zero.py``) and the host parity
    references, so the trained model and its oracle can never drift."""
    h = jnp.dot(x, p.w1, preferred_element_type=jnp.float32) + p.b1
    h = jax.nn.gelu(h)
    return jnp.dot(h, p.w2, preferred_element_type=jnp.float32) + p.b2


def param_specs() -> MLPParams:
    return MLPParams(
        w1=P(None, TP_AXIS), b1=P(TP_AXIS), w2=P(TP_AXIS, None), b2=P(None)
    )


def _forward_local(p: MLPParams, x, overlap: Optional[bool] = False,
                   mesh_axes=(DP_AXIS, TP_AXIS), wire_dtype=None):
    """Per-rank forward; ``overlap`` picks the TP datapath (same math).
    None follows the session default and the tuned size registers
    (``cm.agmm_engages``/``mmrs_engages``, resolved at trace = build
    time); an explicit True forces the fused kernels at any size.
    ``wire_dtype`` stages the collective-matmul ring payloads
    compressed (None: session default ``ACCLConfig.cmatmul_wire_dtype``;
    "off": full precision) — f32 accumulation on-chip either way."""
    from ..ops import collective_matmul as cm

    tp = lax.axis_size(TP_AXIS)
    rows = x.shape[0]
    h_loc = p.w1.shape[1]
    # take the restructured datapath only when the fused kernels would
    # ACTUALLY engage for both stages (session registers + VMEM plan +
    # rung) — its unfused rendition re-gathers rows every rank already
    # holds and would be strictly slower than the psum baseline
    if (tp > 1 and rows % tp == 0
            and cm.agmm_engages(rows // tp, x.shape[1], h_loc, tp,
                                x.dtype, overlap, wire_dtype=wire_dtype,
                                w_dtype=p.w1.dtype)
            and cm.mmrs_engages(rows, h_loc, p.w2.shape[1], tp,
                                x.dtype, overlap, wire_dtype=wire_dtype,
                                w_dtype=p.w2.dtype)):
        # overlapped datapath: the column-parallel matmul regenerates
        # the full batch rows from each rank's row shard hop by hop
        # (x is tp-replicated, so the shards ARE x's row blocks), and
        # the row-parallel combine folds each hop's partial block into
        # the travelling accumulator — MXU busy while ICI moves
        ms = rows // tp
        x_s = lax.dynamic_slice_in_dim(
            x, lax.axis_index(TP_AXIS) * ms, ms, axis=0)
        h = dapi.all_gather_matmul(x_s, p.w1, axis=TP_AXIS,
                                   mesh_axes=mesh_axes,
                                   overlap=overlap,
                                   wire_dtype=wire_dtype) + p.b1
        h = jax.nn.gelu(h)
        y_s = dapi.matmul_reduce_scatter(h.astype(x.dtype), p.w2,
                                         axis=TP_AXIS, mesh_axes=mesh_axes,
                                         overlap=overlap,
                                         wire_dtype=wire_dtype)
        # rebuild the dp-rank's full rows (the scattered halves of the
        # psum: all_gather(psum_scatter(p)) == psum(p))
        y = lax.all_gather(y_s, TP_AXIS, axis=0, tiled=True) + p.b2
        return y
    h = jnp.dot(x, p.w1, preferred_element_type=jnp.float32) + p.b1
    h = jax.nn.gelu(h)
    y_partial = jnp.dot(h, p.w2, preferred_element_type=jnp.float32)
    y = lax.psum(y_partial, TP_AXIS) + p.b2   # row-parallel combine
    return y


def make_mesh(devices, dp: int, tp: int) -> Mesh:
    devs = np.array(list(devices)[: dp * tp]).reshape(dp, tp)
    return Mesh(devs, (DP_AXIS, TP_AXIS))


def make_forward(mesh: Mesh, overlap: Optional[bool] = None,
                 wire_dtype=None):
    """Jitted forward over the (dp, tp) mesh. ``overlap`` picks the TP
    datapath (None: session default; see the module docstring);
    ``wire_dtype`` the collective-matmul wire staging."""
    specs = param_specs()
    axes = tuple(mesh.axis_names)

    def fwd(p, x):
        return _forward_local(p, x, overlap=overlap, mesh_axes=axes,
                              wire_dtype=wire_dtype)

    return jax.jit(
        shard_map(fwd, mesh=mesh, in_specs=(specs, P(DP_AXIS, None)),
                  out_specs=P(DP_AXIS, None), check_vma=False)
    )


def make_train_step(mesh: Mesh, lr: float = 1e-2,
                    overlap: Optional[bool] = None,
                    wire_dtype=None):
    """One fused program: forward + backward + dp gradient allreduce + SGD.

    Returns ``step(params, x, targets) -> (new_params, loss)`` with params
    living sharded on device between steps (no host round-trips — the
    framework's north-star property applied to training). With
    ``overlap`` the TP matmuls of BOTH passes ride the collective-matmul
    kernels (their custom VJPs are each other's duals), producing the
    same loss trajectory as the psum baseline to float tolerance. With
    ``wire_dtype`` BOTH passes' ring payloads (shards, travelling
    accumulators, gathered wgrad operands) ride the ICI compressed
    while every accumulation stays f32 (tolerance-bounded; see
    docs/kernels.md).
    """
    specs = param_specs()
    dp_size = mesh.shape[DP_AXIS]
    axes = tuple(mesh.axis_names)

    def local_step(p: MLPParams, x, t):
        def loss_fn(p_):
            y = _forward_local(p_, x, overlap=overlap, mesh_axes=axes,
                               wire_dtype=wire_dtype)
            return jnp.mean((y - t) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        # DP gradient sync — the collective a training framework runs every
        # step, fused here into the same program as compute
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, DP_AXIS) / dp_size, grads
        )
        new_p = jax.tree_util.tree_map(lambda w, g: w - lr * g, p, grads)
        loss = lax.psum(loss, DP_AXIS) / dp_size
        return new_p, loss

    return jax.jit(
        shard_map(
            local_step, mesh=mesh,
            in_specs=(specs, P(DP_AXIS, None), P(DP_AXIS, None)),
            out_specs=(specs, P()),
            check_vma=False,
        )
    )


def shard_params(params: MLPParams, mesh: Mesh) -> MLPParams:
    specs = param_specs()
    return jax.tree_util.tree_map(
        lambda w, s: jax.device_put(w, NamedSharding(mesh, s)), params, specs
    )
