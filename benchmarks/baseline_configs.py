#!/usr/bin/env python3
"""Run the five BASELINE.json configs and emit one JSON document.

Multi-rank configs run on the CPU-emulator rung (the reference's numbers
for multi-rank also come from its emulator in CI — SURVEY.md §4); the
single-chip datapath row comes from ``bench.py`` on the real TPU. CPU-rung
results are correctness evidence, never device numbers.

Usage::

    XLA_FLAGS=--xla_force_host_platform_device_count=16 JAX_PLATFORMS=cpu \
        python benchmarks/baseline_configs.py [--quick]

Payload sweeps are capped on the emulator (a 1 GiB fp32 global array is
8 GiB × several copies on one CPU host); the cap is recorded in the output
so no row silently pretends to be something it is not.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _p50(samples) -> float:
    return float(np.percentile(np.asarray(samples), 50))


def config_pingpong(quick: bool) -> dict:
    """Send/Recv ping-pong fp32, 2 ranks — p50 one-way latency through the
    full protocol stack (matching engine, rx pool, segmentation)."""
    import jax
    import accl_tpu
    from accl_tpu import dataType

    acc = accl_tpu.ACCL(devices=jax.devices()[:2])
    out = []
    for count in (256, 4096):  # 1 KiB / 16 KiB fp32
        s = acc.create_buffer(count, dataType.float32)
        r = acc.create_buffer(count, dataType.float32)
        s.host[:] = np.random.randn(2, count).astype(np.float32)
        reps = 20 if quick else 100
        # warm the program caches
        acc.send(s, count, src=0, dst=1, tag=1)
        acc.recv(r, count, src=0, dst=1, tag=1)
        ts = []
        for i in range(reps):
            t0 = time.perf_counter()
            acc.send(s, count, src=0, dst=1, tag=2)
            acc.recv(r, count, src=0, dst=1, tag=2)
            acc.send(r, count, src=1, dst=0, tag=3)
            acc.recv(s, count, src=1, dst=0, tag=3)
            ts.append((time.perf_counter() - t0) / 2)  # one-way
        out.append({"count": count, "bytes": count * 4,
                    "p50_oneway_us": round(_p50(ts) * 1e6, 1)})
    acc.deinit()
    return {"config": "sendrecv_pingpong_fp32_2ranks", "rows": out}


def config_ring_allreduce(quick: bool) -> dict:
    """Ring allreduce fp32/fp16, 8 ranks, power-of-2 sweep. Emulator cap:
    16 MiB per-rank payload (fp32) instead of the nominal 1 GiB."""
    import jax
    import accl_tpu
    from accl_tpu import Algorithm, dataType
    from accl_tpu.bench import harness

    acc = accl_tpu.ACCL(devices=jax.devices()[:8])
    comm = acc.global_comm()
    pows = [0, 4, 10, 16, 20, 22] if not quick else [0, 10, 16]
    rows = []
    for dt in (dataType.float32, dataType.float16):
        sweep = harness.run_sweep(
            comm, ["allreduce"], dt=dt, algorithm=Algorithm.RING,
            pows=pows, mode="block", reps=3 if quick else 7)
        for r in sweep:
            rows.append({"dtype": dt.name, "count": r.count,
                         "bytes": r.nbytes,
                         "p50_us": round(r.duration_ns / 1e3, 1),
                         "algbw_GBps": round(r.algbw_GBps, 3)})
    acc.deinit()
    return {"config": "ring_allreduce_8ranks_sweep",
            "cap_note": "emulator sweep capped at 2^22 elems (16 MiB fp32)",
            "rows": rows}


def config_uneven_rooted(quick: bool) -> dict:
    """Bcast + scatter + gather with uneven (non-power-of-2, non-divisible)
    int32 counts — correctness + p50 per-call latency."""
    import jax
    import accl_tpu
    from accl_tpu import dataType

    acc = accl_tpu.ACCL(devices=jax.devices()[:8])
    W = acc.world_size
    rng = np.random.default_rng(7)
    rows = []
    reps = 5 if quick else 25
    for count in (1, 33, 1021, 9973):  # uneven/prime chunk counts
        b = acc.create_buffer(count, dataType.int32)
        s = acc.create_buffer(count * W, dataType.int32)
        r = acc.create_buffer(count, dataType.int32)
        g = acc.create_buffer(count * W, dataType.int32)
        b.host[:] = rng.integers(-99, 99, (W, count))
        s.host[:] = rng.integers(-99, 99, (W, count * W))
        # expectations captured BEFORE the calls mutate the buffers — a
        # wrong-root bcast must fail the check, not define it
        bcast_expect = b.host[3].copy()
        scatter_src = s.host[2].copy()
        row = {"count": count}
        for name, call, check in (
            ("bcast", lambda: acc.bcast(b, count, 3),
             lambda: np.array_equal(b.host, np.tile(bcast_expect, (W, 1)))),
            ("scatter", lambda: acc.scatter(s, r, count, 2),
             lambda: all(np.array_equal(
                 r.host[k], scatter_src[k * count:(k + 1) * count])
                 for k in range(W))),
            ("gather", lambda: acc.gather(r, g, count, 5),
             lambda: np.array_equal(g.host[5], r.host.reshape(-1))),
        ):
            call()  # warm + correctness
            assert check(), f"{name} count={count} mismatch"
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                call()
                ts.append(time.perf_counter() - t0)
            row[f"{name}_p50_us"] = round(_p50(ts) * 1e6, 1)
        rows.append(row)
    acc.deinit()
    return {"config": "bcast_scatter_gather_uneven_int32",
            "correctness": "bit-exact", "rows": rows}


def config_bf16_pallas_16(quick: bool) -> dict:
    """All-gather + reduce-scatter bf16, 16 ranks, Pallas sum plugin."""
    import jax
    import accl_tpu
    from accl_tpu import Algorithm, dataType
    from accl_tpu.bench import harness

    devs = jax.devices()
    if len(devs) < 16:
        return {"config": "allgather_reduce_scatter_bf16_16ranks",
                "skipped": f"needs 16 devices, have {len(devs)} "
                           "(run with --xla_force_host_platform_device_count=16)"}
    acc = accl_tpu.ACCL(devices=devs[:16])
    comm = acc.global_comm()
    pows = [10, 16, 20] if not quick else [10, 16]
    rows = []
    for op in ("allgather", "reduce_scatter"):
        sweep = harness.run_sweep(
            comm, [op], dt=dataType.bfloat16, algorithm=Algorithm.XLA,
            pows=pows, mode="block", reps=3 if quick else 7)
        for r in sweep:
            rows.append({"op": op, "count": r.count, "bytes": r.nbytes,
                         "p50_us": round(r.duration_ns / 1e3, 1),
                         "algbw_GBps": round(r.algbw_GBps, 3)})
    acc.deinit()
    return {"config": "allgather_reduce_scatter_bf16_16ranks",
            "plugin": "Pallas sum lanes on TPU; jnp on the CPU emulator",
            "rows": rows}


def config_hier_2d(quick: bool) -> dict:
    """Hierarchical reduce→bcast allreduce on a 2D mesh. Emulator cap:
    64 MiB payload instead of the nominal 1 GiB."""
    import jax
    import accl_tpu
    from accl_tpu import Algorithm, dataType
    from accl_tpu.bench import harness

    acc = accl_tpu.ACCL(devices=jax.devices()[:8])
    comm = acc.global_comm()
    pows = [20, 24] if not quick else [16]
    sweep = harness.run_sweep(
        comm, ["allreduce"], algorithm=Algorithm.HIERARCHICAL,
        pows=pows, mode="block", reps=3)
    rows = [{"count": r.count, "bytes": r.nbytes,
             "p50_us": round(r.duration_ns / 1e3, 1),
             "algbw_GBps": round(r.algbw_GBps, 3)} for r in sweep]
    acc.deinit()
    return {"config": "hierarchical_2d_reduce_bcast_allreduce",
            "mesh": "2x4 factorization of the 8-device emulator mesh",
            "cap_note": "emulator payload capped at 2^24 elems (64 MiB fp32)",
            "rows": rows}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced reps/sizes (CI smoke)")
    ap.add_argument("--out", default=None, help="write JSON here")
    args = ap.parse_args()

    import jax
    results = {
        "backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "configs": [
            config_pingpong(args.quick),
            config_ring_allreduce(args.quick),
            config_uneven_rooted(args.quick),
            config_bf16_pallas_16(args.quick),
            config_hier_2d(args.quick),
        ],
    }
    text = json.dumps(results, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()
