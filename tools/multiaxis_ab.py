"""Warm call time of each collective family on a TPU host, side by side.

    python tools/multiaxis_ab.py [--out results.jsonl]

For allreduce, reduce_scatter and allgather at 16 MiB, 128 MiB and
1 GiB per rank (nccl-tests' S: the allreduce buffer, the reduce-scatter
input, the allgather output), through one ``accl_tpu.ACCL()`` session
over every chip of the host, it times these families, each an explicit
request except ``auto``:

* ``xla``: XLA's own collective (``Algorithm.XLA``);
* ``multiaxis_c1``: the sequential multi-axis schedule
  (``Algorithm.MULTIAXIS``, ``sched_pipeline_chunks=1``);
* ``multiaxis_c4``: the chunk-pipelined one (``sched_pipeline_chunks=4``);
* ``pallas_bidir``: the Pallas ring kernels, bidirectional;
* ``auto``: whatever AUTO dispatches (its algorithm is in the record).

Every call is synchronous on device-resident buffers and timed from the
call to its output being ready; two warm-up calls, then ``--calls``
timed ones, of which the median is reported. Inputs are whole multiples
of 2**-10, so every family's output must equal XLA's bit for bit, and
is checked. Prints one JSON line per (op, size, family) and a table; a
host with no TPU is an error unless ``--cpu`` is given, which is for
rehearsing the script at small ``--sizes``.
"""
import argparse
import json
import os
import re
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

MIB = 1 << 20
OPS = ("allreduce", "reduce_scatter", "allgather")
FAMILIES = ("xla", "multiaxis_c1", "multiaxis_c4", "pallas_bidir", "auto")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sizes", default=f"{16 * MIB},{128 * MIB},{1024 * MIB}",
                    help="comma-separated bytes per rank (nccl S)")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--out", default=None, help="also write the records here")
    ap.add_argument("--cpu", action="store_true",
                    help="allow a host with no TPU (rehearsal only)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu:
        print(f"multiaxis_ab: no TPU (found {dev.platform}); --cpu "
              "rehearses on the CPU", file=sys.stderr)
        return 1
    import accl_tpu
    from accl_tpu import Algorithm, dataType, reduceFunction
    from accl_tpu.obs import metrics
    from accl_tpu.utils import compile_cache

    compile_cache.enable(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    acc = accl_tpu.ACCL()
    W = acc.world_size
    sh = acc.global_comm().sharding()
    base = acc.config
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": W}
    sink = None
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        sink = open(args.out, "w")

    def family_kw(fam):
        cfg = base
        if fam.startswith("multiaxis"):
            cfg = base.replace(sched_pipeline_chunks=int(fam[-1]))
            return cfg, dict(algorithm=Algorithm.MULTIAXIS)
        if fam == "pallas_bidir":
            return base.replace(bidirectional_rings=True), dict(
                algorithm=Algorithm.PALLAS)
        if fam == "xla":
            return cfg, dict(algorithm=Algorithm.XLA)
        return cfg, {}

    def dispatched():
        """Every ``accl_calls_total`` series and its count."""
        return {k: v for k, v in metrics.snapshot()["counters"].items()
                if k.startswith("accl_calls_total")}

    def put(elems, seed):
        lim = 1 << 20

        def make(key):
            return jax.random.randint(key, (W, elems), -lim, lim,
                                      jnp.int32).astype(jnp.float32) \
                * np.float32(2.0 ** -10)
        buf = acc.create_buffer(elems, dataType.float32)
        buf.device_store(jax.jit(make, out_shardings=sh)(
            jax.random.PRNGKey(seed)))
        return buf

    rows = []
    for op in OPS:
        for S in (int(s) for s in args.sizes.split(",")):
            n = S // 4
            if op == "allreduce":
                in_e, out_e, count = n, n, n
            elif op == "reduce_scatter":
                in_e, out_e, count = n, n // W, n // W
            else:
                in_e, out_e, count = n // W, n, n // W
            src = put(in_e, S + len(op))
            dst = acc.create_buffer(out_e, dataType.float32)
            ref = None
            for fam in FAMILIES:
                cfg, kw = family_kw(fam)
                acc.config = cfg
                if op == "allreduce":
                    def call():
                        acc.allreduce(src, dst, count, reduceFunction.SUM,
                                      from_device=True, to_device=True, **kw)
                elif op == "reduce_scatter":
                    def call():
                        acc.reduce_scatter(src, dst, count,
                                           reduceFunction.SUM,
                                           from_device=True, to_device=True,
                                           **kw)
                else:
                    def call():
                        acc.allgather(src, dst, count, from_device=True,
                                      to_device=True, **kw)
                seen = dispatched()
                times = []
                for i in range(2 + args.calls):
                    t0 = time.perf_counter()
                    call()
                    jax.block_until_ready(dst.device_view())
                    if i >= 2:
                        times.append(time.perf_counter() - t0)
                algo = sorted({re.search(r'algorithm="([^"]+)"', k)[1]
                               for k, v in dispatched().items()
                               if f'op="{op}"' in k and v > seen.get(k, 0)})
                out = dst.device_view()
                if ref is None:
                    ref = jnp.copy(out)
                    exact = True
                else:
                    exact = bool(jnp.array_equal(out, ref))
                acc.config = base
                rec = {"op": op, "bytes_per_rank": S, "family": fam,
                       "median_ms": statistics.median(times) * 1e3,
                       "min_ms": min(times) * 1e3, "calls": len(times),
                       "exact_vs_xla": exact,
                       "dispatched": algo,
                       "device": device}
                rows.append(rec)
                line = json.dumps(rec)
                print(line, flush=True)
                if sink:
                    sink.write(line + "\n")
                    sink.flush()
            del src, dst, ref, out
    print(f"\n{'op':<15}{'bytes':>11}"
          + "".join(f"{f:>14}" for f in FAMILIES))
    for op in OPS:
        for S in (int(s) for s in args.sizes.split(",")):
            r = {x["family"]: x["median_ms"] for x in rows
                 if x["op"] == op and x["bytes_per_rank"] == S}
            print(f"{op:<15}{S:>11}"
                  + "".join(f"{r[f]:>14.3f}" for f in FAMILIES))
    acc.deinit()
    ok = all(x["exact_vs_xla"] for x in rows)
    if not ok:
        print("multiaxis_ab: a family's output differs from XLA's",
              file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
