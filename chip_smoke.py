#!/usr/bin/env python3
"""Chip smoke: ACCL-TPU's main path, once, on the TPU, checked against
plain numpy / jax.numpy references.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --chips 4    # the four chips of one v5e host

One chip: the session (``accl_tpu.ACCL()``), its buffer manager and
Pallas plugin datapath at the two largest sizes of ``bench.py``'s sweep
(64 and 256 MiB), and the model tier's kernels at real widths (flash
attention forward + fused backward, paged flash decode). Four chips: the
collectives, which exist only across chips, and the collective matmul
— and no other phase.

Every earlier stdout line is one JSON record per phase: compile and run
seconds, max abs error against the reference, ``peak_bytes_in_use`` and
the Mosaic kernels found in the compiled module. The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed and every fallback counter stayed at zero. With
no TPU the script exits non-zero and prints no result.

``compile_s`` of a session phase is its first call less a warm call; of
a kernel phase, the AOT compile itself. Neither is a performance result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

MIB = 1 << 20
#: the two largest points of bench.py's sweep (SWEEP_POWS 24, 26: 2^24
#: and 2^26 f32 elements)
BUCKET_BYTES = (64 * MIB, 256 * MIB)
#: BASELINE.json's hierarchical config: 1 GiB per rank
BIG_BYTES = 1024 * MIB
#: every fallback counter the main path keeps; all must read zero
FALLBACK_COUNTERS = ("accl_algorithm_fallback_total",
                     "accl_flash_decode_fallback_total",
                     "accl_flash_prefill_fallback_total",
                     "accl_cmatmul_fallback_total")
MOSAIC = 'custom_call_target="tpu_custom_call"'


class SmokeError(AssertionError):
    """A phase produced a wrong result or took a path it must not."""


def _now() -> float:
    return time.perf_counter()


def _peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _kernels(compiled, want: int = 1) -> int:
    """Mosaic kernels in a compiled module: a kernel that ran in
    interpret mode or was replaced by a lax reference leaves none."""
    n = compiled.as_text().count(MOSAIC)
    if n < want:
        raise SmokeError(f"{n} Mosaic kernels in the compiled module, "
                         f"expected >= {want}")
    return n


def _check(name: str, got, want, atol: float, rtol: float = 0.0) -> float:
    """Max abs error of ``got`` vs ``want``; raises past atol + rtol*|want|."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise SmokeError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.all(np.isfinite(got)):
        raise SmokeError(f"{name}: non-finite values")
    err = np.abs(got - want)
    bound = atol + rtol * np.abs(want)
    if np.any(err > bound):
        i = int(np.argmax(err - bound))
        raise SmokeError(f"{name}: max abs error {float(err.max())} "
                         f"(worst at flat {i}: {got.flat[i]} vs "
                         f"{want.flat[i]})")
    return float(err.max())


def fallbacks() -> dict:
    """Each fallback counter summed over its labels."""
    from accl_tpu.obs import metrics

    totals = dict.fromkeys(FALLBACK_COUNTERS, 0.0)
    for key, v in metrics.snapshot()["counters"].items():
        name = key.split("{")[0]
        if name in totals:
            totals[name] += v
    return totals


class Smoke:
    """Runs phases, prints one record each, remembers failures."""

    def __init__(self, devices):
        self.devices = devices
        self.failed: list = []

    def phase(self, name: str, fn) -> None:
        t0 = _now()
        try:
            rec = fn() or {}
        except Exception as e:  # noqa: BLE001 — record, then go on
            traceback.print_exc()
            self.failed.append(name)
            rec = {"error": f"{type(e).__name__}: {e}"[:2000]}
        rec = {"phase": name, **rec,
               "wall_s": round(_now() - t0, 3),
               "peak_bytes": _peak_bytes(self.devices),
               "fallbacks": fallbacks()}
        print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# one chip: session, plugin datapath, model-tier kernels
# ---------------------------------------------------------------------------

def _session_call(call, out_buf):
    """(first_s, warm_s) of a session call writing ``out_buf`` on device
    (to_device=True); the host mirror is synced afterwards."""
    import jax

    times = []
    for _ in range(2):
        t0 = _now()
        call()
        jax.block_until_ready(out_buf.device_view())
        times.append(_now() - t0)
    out_buf.sync_from_device()
    return {"compile_s": round(max(times[0] - times[1], 0.0), 4),
            "run_s": round(times[1], 6)}


def datapath_phases(smoke: Smoke, acc, rng, sizes=BUCKET_BYTES) -> None:
    """The session's plugin datapath at bucket sizes (world 1: every
    collective resolves to Algorithm.XLA; the Pallas kernels are the
    combine and compression lanes)."""
    import jax
    import jax.numpy as jnp

    from accl_tpu import dataType, reduceFunction
    from accl_tpu.ops import compression
    from accl_tpu.parallel import primitives

    comm = acc.global_comm()
    f32 = dataType.float32
    for nbytes in sizes:
        n = nbytes // 4
        tag = f"{nbytes // MIB}MiB"
        a = acc.create_buffer(n, f32, host_data=rng.standard_normal(
            (1, n), dtype=np.float32))
        b = acc.create_buffer(n, f32, host_data=rng.standard_normal(
            (1, n), dtype=np.float32))
        r = acc.create_buffer(n, f32)
        a.sync_to_device()
        b.sync_to_device()

        for func, ref in ((reduceFunction.SUM, np.add),
                          (reduceFunction.MAX, np.maximum)):
            def combine(func=func, ref=ref):
                rec = _session_call(lambda: acc.combine(
                    n, func, a, b, r, val1_from_device=True,
                    val2_from_device=True, to_device=True), r)
                rec["max_abs_err"] = _check("combine", r.host,
                                            ref(a.host, b.host), 0.0)
                # the program the session ran, from the same builder
                prog = primitives.build_combine(
                    comm, func, f32, use_pallas=acc.config.use_pallas
                    and acc.config.enable_arith)
                rec["kernels"] = _kernels(jax.jit(prog).lower(
                    a.device_view(), b.device_view()).compile())
                return rec
            smoke.phase(f"combine_{func.name.lower()}_{tag}", combine)

        def copy():
            rec = _session_call(lambda: acc.copy(
                a, r, n, from_device=True, to_device=True), r)
            rec["max_abs_err"] = _check("copy", r.host, a.host, 0.0)
            return rec
        smoke.phase(f"copy_{tag}", copy)

        def world1(name, call, want):
            def run():
                rec = _session_call(call, r)
                rec["max_abs_err"] = _check(name, r.host, want, 0.0)
                return rec
            smoke.phase(f"{name}_{tag}", run)

        world1("allreduce", lambda: acc.allreduce(
            a, r, n, reduceFunction.SUM, from_device=True, to_device=True),
            a.host)
        world1("allgather", lambda: acc.allgather(
            a, r, n, from_device=True, to_device=True), a.host)
        world1("reduce_scatter", lambda: acc.reduce_scatter(
            a, r, n, reduceFunction.SUM, from_device=True, to_device=True),
            a.host)
        world1("alltoall", lambda: acc.alltoall(
            a, r, n, from_device=True, to_device=True), a.host)

        def bcast():
            r.host = a.host.copy()
            r.sync_to_device()
            rec = _session_call(lambda: acc.bcast(
                r, n, root=0, from_device=True, to_device=True), r)
            rec["max_abs_err"] = _check("bcast", r.host, a.host, 0.0)
            return rec
        smoke.phase(f"bcast_{tag}", bcast)

        def cast_roundtrip():
            def fn(x):
                return compression.pallas_cast(
                    compression.pallas_cast(x, jnp.bfloat16), jnp.float32)
            x = a.device_view()
            t0 = _now()
            compiled = jax.jit(fn).lower(x).compile()
            t1 = _now()
            got = jax.block_until_ready(compiled(x))
            t2 = _now()
            want = x.astype(jnp.bfloat16).astype(jnp.float32)
            return {"compile_s": round(t1 - t0, 4),
                    "run_s": round(t2 - t1, 6),
                    "max_abs_err": _check("cast", got, want, 0.0),
                    "kernels": _kernels(compiled, 2)}
        smoke.phase(f"pallas_cast_roundtrip_{tag}", cast_roundtrip)

        def stochastic():
            def fn(x, seed):
                return compression.pallas_compress_stochastic(
                    x, jnp.bfloat16, seed=seed)
            x = a.device_view()
            seed = jnp.int32(int(rng.integers(1, 1 << 30)))
            t0 = _now()
            compiled = jax.jit(fn).lower(x, seed).compile()
            t1 = _now()
            got = jax.block_until_ready(compiled(x, seed))
            t2 = _now()
            # each element rounds to one of its two bf16 neighbours, and
            # not always to the nearer one; the rounding is unbiased
            near = x.astype(jnp.bfloat16)
            ulp = jnp.abs(near.astype(jnp.float32)) * 2.0 ** -7 + 1e-30
            err = jnp.abs(got.astype(jnp.float32) - x)
            if bool(jnp.any(err > ulp)):
                raise SmokeError("stochastic rounding off the bf16 grid")
            moved = float(jnp.mean((got != near).astype(jnp.float32)))
            if not 0.05 < moved < 0.95:
                raise SmokeError(f"{moved:.3f} of elements left the "
                                 f"nearest bf16 value")
            bias = float(jnp.mean(got.astype(jnp.float32) - x)
                         / jnp.mean(jnp.abs(x)))
            if abs(bias) > 1e-3:
                raise SmokeError(f"stochastic rounding biased: {bias}")
            return {"compile_s": round(t1 - t0, 4),
                    "run_s": round(t2 - t1, 6),
                    "max_abs_err": float(jnp.max(err)),
                    "rounded_away_share": round(moved, 4),
                    "kernels": _kernels(compiled)}
        smoke.phase(f"pallas_compress_stochastic_{tag}", stochastic)

        def cmdlist():
            r1 = acc.create_buffer(n, f32)
            r2 = acc.create_buffer(n, f32)
            cl = acc.command_list()
            cl.allreduce(a, r1, n, reduceFunction.SUM)
            cl.bcast(r1, n, root=0)
            cl.combine(n, reduceFunction.SUM, r1, b, r2)
            t0 = _now()
            cl.execute()
            t1 = _now()
            err1 = _check("cmdlist", r2.host, a.host + b.host, 0.0)
            r2.device_store(jnp.zeros_like(r2.device_view()))
            t2 = _now()
            req = cl.execute(sync=False, from_device=True)
            req.wait()
            jax.block_until_ready(r2.device_view())
            t3 = _now()
            r2.sync_from_device()
            err2 = _check("cmdlist from_device", r2.host,
                          a.host + b.host, 0.0)
            return {"compile_s": round(max((t1 - t0) - (t3 - t2), 0.0), 4),
                    "run_s": round(t3 - t2, 6),
                    "max_abs_err": max(err1, err2)}
        smoke.phase(f"cmdlist_{tag}", cmdlist)

        if nbytes == sizes[0]:
            def sendrecv():
                if nbytes <= acc.config.max_eager_size:
                    raise SmokeError("payload does not take rendezvous")
                r.host = np.zeros_like(a.host)
                r.sync_to_device()
                t0 = _now()
                req = acc.send(a, n, src=0, dst=0, tag=7, from_device=True,
                               run_async=True)
                acc.recv(r, n, src=0, dst=0, tag=7)
                req.wait()
                t1 = _now()
                return {"run_s": round(t1 - t0, 6),
                        "max_abs_err": _check("self send/recv", r.host,
                                              a.host, 0.0)}
            smoke.phase(f"self_sendrecv_{tag}", sendrecv)
        del a, b, r


def _attention_ref(q, k, v, causal: bool):
    """Softmax attention in f32, per head: (H, S, d) -> (H, S, d)."""
    import jax
    import jax.numpy as jnp

    def head(qkv):
        qh, kh, vh = (t.astype(jnp.float32) for t in qkv)
        s = qh @ kh.T / np.float32(np.sqrt(qh.shape[-1]))
        if causal:
            n = s.shape[0]
            s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    return jax.lax.map(head, (q, k, v), batch_size=4)


def flash_phases(smoke: Smoke, rng, H=32, S=4096, d=128) -> None:
    """flash_attention forward and jax.grad (fused backward) at B=1,
    bf16, causal and non-causal, against softmax attention in f32."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.ops import flash

    key = jax.random.PRNGKey(int(rng.integers(1 << 31)))
    kq, kk, kv, kg = jax.random.split(key, 4)
    q, k, v = (jax.random.normal(kk_, (H, S, d), jnp.bfloat16)
               for kk_ in (kq, kk, kv))
    cot = jax.random.normal(kg, (H, S, d), jnp.float32)

    for causal in (False, True):
        def run(causal=causal):
            # the cotangent is an argument: closed over, it would be
            # baked into the executable as a 64 MiB constant
            def loss(q_, k_, v_, c):
                out = flash.flash_attention(q_, k_, v_, causal=causal,
                                            bwd_mode="fused")
                return jnp.sum(out.astype(jnp.float32) * c), out

            def ref_loss(q_, k_, v_, c):
                out = _attention_ref(q_, k_, v_, causal)
                return jnp.sum(out * c), out

            step = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
            t0 = _now()
            compiled = jax.jit(step).lower(q, k, v, cot).compile()
            t1 = _now()
            (_, out), grads = jax.block_until_ready(compiled(q, k, v, cot))
            t2 = _now()
            (_, out_ref), grads_ref = jax.jit(jax.value_and_grad(
                ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v, cot)
            # bf16 operands and outputs: tolerance relative to the
            # reference's scale, per tensor
            errs = []
            for name, g, w in (("out", out, out_ref),
                               ("dq", grads[0], grads_ref[0]),
                               ("dk", grads[1], grads_ref[1]),
                               ("dv", grads[2], grads_ref[2])):
                w = np.asarray(w, np.float32)
                scale = float(np.max(np.abs(w)))
                errs.append(_check(f"flash {name}", g, w,
                                   atol=2e-2 * scale))
            return {"compile_s": round(t1 - t0, 4),
                    "run_s": round(t2 - t1, 6),
                    "max_abs_err": max(errs),
                    "kernels": _kernels(compiled, 2)}
        smoke.phase(f"flash_fwd_bwd_{'causal' if causal else 'full'}"
                    f"_S{S}", run)


def decode_phase(smoke: Smoke, rng, B=32, H=32, hkv=8, d=128, page=128,
                 ctx=4096) -> None:
    """Paged flash_decode: B slots of up to ``ctx`` tokens in ``page``-
    token pages scattered over the pool, GQA H:hkv, bf16."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.ops import flash

    def run():
        pages_max = ctx // page
        n_pages = B * pages_max
        key = jax.random.PRNGKey(int(rng.integers(1 << 31)))
        k1, k2, k3 = jax.random.split(key, 3)
        q = jax.random.normal(k1, (B, H, d), jnp.bfloat16)
        kp = jax.random.normal(k2, (hkv, n_pages, page, d), jnp.bfloat16)
        vp = jax.random.normal(k3, (hkv, n_pages, page, d), jnp.bfloat16)
        tables_np = rng.permutation(n_pages).reshape(B, pages_max)
        lens_np = rng.integers(1, ctx + 1, B)
        lens_np[0] = ctx           # one full context
        tables = jnp.asarray(tables_np, jnp.int32)
        lens = jnp.asarray(lens_np, jnp.int32)
        t0 = _now()
        compiled = jax.jit(flash.flash_decode).lower(
            q, kp, vp, tables, lens).compile()
        t1 = _now()
        got = jax.block_until_ready(compiled(q, kp, vp, tables, lens))
        t2 = _now()

        def ref(q, kp, vp, tables, lens):
            # gather each slot's chain -> (B, hkv, ctx, d); group q heads
            kc = kp[:, tables].reshape(hkv, B, ctx, d).transpose(1, 0, 2, 3)
            vc = vp[:, tables].reshape(hkv, B, ctx, d).transpose(1, 0, 2, 3)
            qg = q.reshape(B, hkv, H // hkv, d).astype(jnp.float32)
            s = jnp.einsum("bhgd,bhtd->bhgt", qg, kc.astype(jnp.float32))
            s = s / np.float32(np.sqrt(d))
            live = jnp.arange(ctx)[None, :] < lens[:, None]
            s = jnp.where(live[:, None, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhgt,bhtd->bhgd", p, vc.astype(jnp.float32))
            return o.reshape(B, H, d)

        want = np.asarray(jax.jit(ref)(q, kp, vp, tables, lens))
        return {"compile_s": round(t1 - t0, 4), "run_s": round(t2 - t1, 6),
                "max_abs_err": _check("flash_decode", got, want,
                                      atol=2e-2 * float(np.abs(want).max())),
                "kernels": _kernels(compiled)}
    smoke.phase(f"flash_decode_paged_B{B}_ctx{ctx}", run)


def one_chip(smoke: Smoke, seed: int) -> None:
    import accl_tpu

    rng = np.random.default_rng(seed)
    acc = accl_tpu.ACCL()
    if acc.world_size != 1:
        raise SmokeError(f"the default run is for one chip; JAX shows "
                         f"{acc.world_size} (use --chips 4)")
    print(json.dumps({"phase": "session", "scan": acc.scan()},
                     default=str), flush=True)
    datapath_phases(smoke, acc, rng)
    flash_phases(smoke, rng)
    decode_phase(smoke, rng)
    acc.deinit()


# ---------------------------------------------------------------------------
# four chips: collectives and the collective matmul
# ---------------------------------------------------------------------------

def collective_phases(smoke: Smoke, acc, rng, small=64 * MIB,
                      big=BIG_BYTES) -> None:
    import jax

    from accl_tpu import Algorithm, dataType, reduceFunction
    from accl_tpu.parallel import pallas_ring

    comm = acc.global_comm()
    W = acc.world_size
    f32, bf16 = dataType.float32, dataType.bfloat16

    def placed(buf):
        """Each rank's shard on its own chip, in rank order."""
        shards = buf.device_view().addressable_shards
        devs = [s.device for s in sorted(shards, key=lambda s: s.index[0].start)]
        if len(set(devs)) != W or devs != list(comm.devices):
            raise SmokeError(f"shards on {devs}, ranks on {comm.devices}")

    for nbytes in (small, big):
        n = nbytes // 4
        tag = f"{nbytes // MIB}MiB"
        a = acc.create_buffer(n, f32, host_data=rng.standard_normal(
            (W, n), dtype=np.float32))
        r = acc.create_buffer(n, f32)
        a.sync_to_device()
        r.sync_to_device()
        placed(a)
        placed(r)
        want = np.broadcast_to(a.host.sum(0, dtype=np.float32), (W, n))
        scale = float(np.abs(want[0]).max())
        variants = (
            ("auto", dict(), None),
            ("xla", dict(algorithm=Algorithm.XLA), None),
            ("ring", dict(algorithm=Algorithm.RING), None),
            ("pallas_bidir", dict(algorithm=Algorithm.PALLAS), True),
            ("pallas_unidir", dict(algorithm=Algorithm.PALLAS), False),
            ("auto_bf16_wire", dict(compress_dtype=bf16), None),
        )
        for name, kw, bidir in variants:
            def run(kw=kw, bidir=bidir, name=name):
                saved = acc.config
                if bidir is not None:
                    acc.config = saved.replace(bidirectional_rings=bidir)
                try:
                    rec = _session_call(lambda: acc.allreduce(
                        a, r, n, reduceFunction.SUM, from_device=True,
                        to_device=True, **kw), r)
                finally:
                    acc.config = saved
                wire = "compress_dtype" in kw
                rec["max_abs_err"] = _check(
                    f"allreduce {name}", r.host, want,
                    atol=(2e-2 * scale if wire else 1e-4 * scale))
                if bidir is not None:
                    prog = pallas_ring.build_pallas_ring_allreduce(
                        comm, reduceFunction.SUM, f32,
                        acc.config.segment_size, bidirectional=bidir)
                    rec["kernels"] = _kernels(
                        jax.jit(prog).lower(a.device_view()).compile(), 2)
                return rec
            smoke.phase(f"allreduce_{name}_{tag}", run)
        del a, r, want

    n = small // 4
    c = n // W
    tag = f"{small // MIB}MiB"
    x = rng.standard_normal((W, n), dtype=np.float32)
    a = acc.create_buffer(n, f32, host_data=x)
    a.sync_to_device()

    def collective(name, out_count, call, want, atol=0.0):
        def run():
            r = acc.create_buffer(out_count, f32)
            r.sync_to_device()
            placed(r)
            rec = _session_call(lambda: call(r), r)
            rec["max_abs_err"] = _check(name, r.host, want, atol=atol)
            return rec
        smoke.phase(f"{name}_{tag}", run)

    collective("allgather", W * n, lambda r: acc.allgather(
        a, r, n, from_device=True, to_device=True),
        np.broadcast_to(x.reshape(-1), (W, W * n)))
    rs_want = x.reshape(W, W, c).sum(0, dtype=np.float32)
    collective("reduce_scatter", c, lambda r: acc.reduce_scatter(
        a, r, c, reduceFunction.SUM, from_device=True, to_device=True),
        rs_want, atol=1e-4 * float(np.abs(rs_want).max()))
    collective("alltoall", n, lambda r: acc.alltoall(
        a, r, c, from_device=True, to_device=True),
        x.reshape(W, W, c).transpose(1, 0, 2).reshape(W, n))
    root = W - 2

    def bcast(r):
        r.host = x.copy()
        r.sync_to_device()
        acc.bcast(r, n, root=root, from_device=True, to_device=True)
    collective("bcast", n, bcast, np.broadcast_to(x[root], (W, n)))
    gather_want = np.zeros((W, W * n), np.float32)
    gather_want[0] = x.reshape(-1)
    collective("gather", W * n, lambda r: acc.gather(
        a, r, n, root=0, from_device=True, to_device=True), gather_want)

    def sendrecv():
        r = acc.create_buffer(n, f32)
        r.sync_to_device()
        t0 = _now()
        req = acc.send(a, n, src=0, dst=W - 1, tag=3, from_device=True,
                       run_async=True)
        acc.recv(r, n, src=0, dst=W - 1, tag=3)
        req.wait()
        t1 = _now()
        return {"run_s": round(t1 - t0, 6),
                "max_abs_err": _check("send/recv 0->3", r.host[W - 1],
                                      x[0], 0.0)}
    smoke.phase(f"sendrecv_0_to_{W - 1}_{tag}", sendrecv)
    del a


def cmatmul_phases(smoke: Smoke, acc, rng, m=4096, k=4096, n=16384) -> None:
    """all_gather_matmul and matmul_reduce_scatter at a ZeRO-3 MLP width
    (d_model=k, d_ff=n, m tokens), bf16, fused ring kernels against the
    unfused ``lax.all_gather`` / ``psum_scatter`` + dot pair."""
    import jax
    import jax.numpy as jnp

    from accl_tpu import Algorithm
    from accl_tpu.parallel import algorithms

    comm = acc.global_comm()
    W = acc.world_size
    sh = comm.sharding()
    key = jax.random.PRNGKey(int(rng.integers(1 << 31)))
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def put(kk, shape):
        return jax.device_put(jax.random.normal(kk, shape, jnp.bfloat16), sh)

    cases = (
        # up-projection: tokens gathered over rows, W1 column-sharded
        ("all_gather_matmul", algorithms.build_allgather_matmul,
         put(k1, (W, m // W, k)), put(k2, (W, k, n // W))),
        # down-projection: partial products reduce-scattered over rows
        ("matmul_reduce_scatter", algorithms.build_matmul_reduce_scatter,
         put(k3, (W, m, n // W)), put(k4, (W, n // W, k))),
    )
    for name, build, x, w in cases:
        def run(build=build, x=x, w=w, name=name):
            t0 = _now()
            fused = jax.jit(build(comm, Algorithm.PALLAS)).lower(
                x, w).compile()
            t1 = _now()
            got = jax.block_until_ready(fused(x, w))
            t2 = _now()
            want = jax.jit(build(comm, Algorithm.XLA))(x, w)
            got, want = (np.asarray(t, np.float32) for t in (got, want))
            scale = float(np.abs(want).max())
            err = _check(name, got, want, atol=2e-2 * scale)
            # and the pair itself against numpy on rank 0's first rows
            xs, ws = (np.asarray(t, np.float32) for t in (x, w))
            rows = min(256, m // W)
            if name == "all_gather_matmul":
                ref = xs.reshape(m, k)[:rows] @ ws[0]
            else:
                ref = sum(xs[i, :rows] @ ws[i] for i in range(W))
            _check(f"{name} (XLA pair vs numpy)", want[0, :rows], ref,
                   atol=2e-2 * scale)
            return {"compile_s": round(t1 - t0, 4),
                    "run_s": round(t2 - t1, 6),
                    "max_abs_err": err,
                    "kernels": _kernels(fused)}
        smoke.phase(f"{name}_m{m}_k{k}_n{n}", run)


def four_chips(smoke: Smoke, seed: int) -> None:
    import accl_tpu

    rng = np.random.default_rng(seed)
    acc = accl_tpu.ACCL()     # snake order over the host's chips
    if acc.world_size != 4:
        raise SmokeError(f"--chips 4 found {acc.world_size} chips")
    print(json.dumps({"phase": "session", "scan": acc.scan()},
                     default=str), flush=True)
    collective_phases(smoke, acc, rng)
    cmatmul_phases(smoke, acc, rng)
    acc.deinit()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: session + kernels (default); 4: collectives "
                         "and collective matmul across a v5e host")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every payload")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found only {dev.platform} "
              f"devices; this check runs on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX found {len(devices)}", file=sys.stderr)
        return 1
    try:
        import accl_tpu
        from accl_tpu.bench import harness
        from accl_tpu.utils import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import accl_tpu ({e}); run it from the "
              f"root of the repository", file=sys.stderr)
        return 1
    try:
        harness.hbm_peak_bytes_per_s()
    except ValueError as e:    # a device kind with no peaks on record
        print(f"chip_smoke: unknown TPU: {e}", file=sys.stderr)
        return 1
    cache = compile_cache.enable(os.path.dirname(os.path.abspath(__file__)))
    print(json.dumps({"phase": "start", "platform": dev.platform,
                      "kind": dev.device_kind, "count": len(devices),
                      "jax": jax.__version__, "compile_cache": cache,
                      "accl_tpu": os.path.dirname(accl_tpu.__file__)}),
          flush=True)

    smoke = Smoke(devices)
    if args.chips == 1:
        smoke.phase("one_chip", lambda: one_chip(smoke, args.seed))
    else:
        smoke.phase("four_chips", lambda: four_chips(smoke, args.seed))

    nonzero = {k: v for k, v in fallbacks().items() if v}
    if smoke.failed or nonzero:
        print(f"chip_smoke: FAILED phases {smoke.failed}, fallback "
              f"counters {nonzero}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
