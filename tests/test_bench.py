"""Sweep harness + analytic model tests (bench.cpp / parse_bench_results.py
analogs, SURVEY.md §2.8)."""
import io

import numpy as np
import pytest

from accl_tpu import Algorithm, dataType, reduceFunction
from accl_tpu.bench import harness, models
from accl_tpu.constants import operation


def test_sweep_produces_rows(accl):
    rows = harness.run_sweep(
        accl.global_comm(), ["allreduce", "bcast"],
        min_pow=4, max_pow=5, reps=1)
    assert len(rows) == 4
    for r in rows:
        assert r.duration_ns > 0
        assert r.algbw_GBps > 0
        assert 0.0 <= r.efficiency <= 1.0
        assert r.world == accl.world_size


def test_sweep_all_ops_one_size(accl):
    ops = ["copy", "combine", "sendrecv", "scatter", "gather",
           "allgather", "reduce", "reduce_scatter", "alltoall"]
    rows = harness.run_sweep(accl.global_comm(), ops,
                             min_pow=4, max_pow=4, reps=1)
    assert [r.op for r in rows] == ops


def test_sweep_ring_algorithm(accl):
    rows = harness.run_sweep(
        accl.global_comm(), ["allreduce"], algorithm=Algorithm.RING,
        min_pow=4, max_pow=4, reps=1)
    assert rows[0].algorithm == "RING"


def test_sweep_rejects_unknown_op(accl):
    with pytest.raises(ValueError, match="unknown ops"):
        harness.run_sweep(accl.global_comm(), ["frobnicate"])


def test_csv_roundtrip(accl):
    rows = harness.run_sweep(accl.global_comm(), ["bcast"],
                             min_pow=4, max_pow=4, reps=1)
    buf = io.StringIO()
    harness.write_csv(rows, buf)
    lines = buf.getvalue().strip().splitlines()
    assert lines[0].startswith("op,algorithm,world,count")
    assert lines[1].startswith("bcast,")
    assert len(lines) == 2


def test_ideal_models_bandwidth_ordering():
    """Ring allreduce moves 2(P-1)/P*M per link -> slower than bcast's
    log2(P) rounds at equal payload only for small P; check exact values."""
    bw, M, P = 100e9, 1 << 30, 8
    ar = models.ideal_duration(operation.allreduce, P, M, bw)
    assert ar == pytest.approx(2 * (P - 1) * (M / P) / bw)
    bc = models.ideal_duration(operation.bcast, P, M, bw)
    assert bc == pytest.approx(3 * M / bw)
    rs = models.ideal_duration(operation.reduce_scatter, P, M, bw)
    assert rs == pytest.approx((P - 1) * (M / P) / bw)


def test_ideal_models_world1_degenerate():
    for op in (operation.allreduce, operation.reduce_scatter,
               operation.alltoall):
        assert models.ideal_duration(op, 1, 1 << 20, 1e9, rtt=5e-6) == 5e-6


def test_efficiency_bounds():
    assert models.efficiency(operation.allreduce, 8, 1 << 20,
                             measured_s=1e-12, bw=1e9) == 1.0
    assert models.efficiency(operation.allreduce, 8, 1 << 20,
                             measured_s=1e3, bw=1e9) < 1e-5
    assert models.efficiency(operation.barrier, 1, 0,
                             measured_s=1.0, bw=1e9) == 0.0


def test_cmatmul_lanes_run_on_interpreter_rung(accl):
    """The collective-matmul overlap lanes run on this rung (kernels or
    not) and follow the resolution protocol: rows for both ops, ratio
    raws always on the record, and the resolved flag true ONLY when the
    fused kernel actually engaged (never on the XLA fallback, whose
    "fused" time measures nothing)."""
    from accl_tpu.bench import lanes
    from accl_tpu.ops import collective_matmul as cm

    rows = lanes.bench_cmatmul(accl.global_comm(), m=8, k=32, n=24,
                               rounds=2)
    assert [r["metric"] for r in rows] == ["cmatmul_ag", "cmatmul_rs"]
    for r in rows:
        assert r["unit"] == "ratio"
        assert r["overlap_plan"] is not None     # tiny shapes fit VMEM
        assert r["fused_engaged"] == cm._kernels_available()
        assert r["resolved"] == r["fused_engaged"]
        assert r["raw_overlap_eff_med"] > 0      # raws always present
        assert r["fused_us"] > 0 and r["matmul_us"] > 0
        if not r["resolved"]:
            assert r["value"] == 0.0


def test_bench_script_lanes_filter(tmp_path):
    """bench.py satellites: --lanes runs a single stage (on-silicon A/B
    workflow) and --trace writes one Chrome-trace file per stage."""
    import json as _json
    import os
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
    # one CPU device: the suite's 9-device XLA_FLAGS would make the child
    # a multi-chip rig whose cmatmul lanes run interpreted at full size
    env = dict(os.environ, JAX_PLATFORMS="cpu", ACCL_BENCH_QUICK="1",
               XLA_FLAGS="")
    # --lanes filter: sweep-only run emits the headline, skips lanes;
    # --trace writes one Chrome-trace JSON per executed stage
    trace_dir = str(tmp_path / "traces")
    r = subprocess.run([sys.executable, script, "--lanes", "sweep",
                        "--trace", trace_dir],
                      timeout=240, capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    out = _json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] != "bench_crashed" and out["sweep"]
    # ISSUE r8: the artifact embeds the metrics snapshot + schema version
    # (the sweep measures compiled programs directly, so the snapshot's
    # guarantee is structural — schema + the three tables always present)
    assert out["obs_schema"] == 1
    assert out["metrics"]["schema"] == 1
    for table in ("counters", "gauges", "histograms"):
        assert isinstance(out["metrics"][table], dict)
    # per-lane trace file: standalone Chrome-trace JSON with the lane span
    with open(os.path.join(trace_dir, "sweep_fused.trace.json")) as f:
        doc = _json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert "lane.sweep_fused" in names
    # a filter naming no stage skips the sweep too (fast no-op run)
    r = subprocess.run([sys.executable, script, "--lanes", "cmatmul_ag"],
                      timeout=240, capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr[-800:]
    out = _json.loads(r.stdout.strip().splitlines()[-1])
    assert out["sweep"] is None
    assert "obs_schema" in out and "metrics" in out


def test_bw_fields_resolution_protocol(monkeypatch):
    """The lane resolution protocol (VERDICT r4 weak #3): flag on the
    MEDIAN slope with a 1.10x cap; the min slope is the headline unless
    it is unphysical or clamped-to-zero (noise-negative), in which case
    the median reports; raw values stay on the record either way."""
    from accl_tpu.bench import harness, lanes

    monkeypatch.setattr(harness, "hbm_peak_bytes_per_s", lambda: 800e9)
    nbytes = 64 << 20
    base = {"per_op_max": 1e-3, "launch": 0.1, "amortized_floor": 1e-3,
            "resolved": True, "k_max": 512, "rounds": 5, "pilot": "hint"}

    def bw(per):  # implied GB/s at 3x traffic for a given slope
        return nbytes / per / 1e9

    # normal: min physical -> min is the headline
    t = dict(base, per_op=3e-4, per_op_med=3.3e-4)
    f = lanes._bw_fields(t, nbytes, 3)
    assert f["resolved"] and f["value"] == round(bw(3e-4), 3)

    # noise-fast min (implied > 1.10x roofline) with healthy median ->
    # median reports, raw min stays on the record
    fast = nbytes * 3 / (800e9 * 2)     # 2x roofline
    t = dict(base, per_op=fast, per_op_med=3.3e-4)
    f = lanes._bw_fields(t, nbytes, 3)
    assert f["resolved"] and f["value"] == round(bw(3.3e-4), 3)
    assert f["raw_GBps"] == round(bw(fast), 3)

    # clamped-to-zero min (noise-negative slope) must NOT report 0.0 on
    # a resolved lane — the regression the round-5 review caught
    t = dict(base, per_op=0.0, per_op_med=3.3e-4)
    f = lanes._bw_fields(t, nbytes, 3)
    assert f["resolved"] and f["value"] == round(bw(3.3e-4), 3)

    # MEDIAN unphysical -> the lane unresolves, value zeroes, raws kept
    t = dict(base, per_op=fast, per_op_med=fast)
    f = lanes._bw_fields(t, nbytes, 3)
    assert not f["resolved"] and f["value"] == 0.0
    assert f["raw_med_GBps"] == round(bw(fast), 3)

    # an honest ~0.98-roofline median survives the 1.10x cap (the old
    # 1.05x min-based cap zeroed exactly this case)
    honest = nbytes * 3 / (800e9 * 0.98)
    t = dict(base, per_op=honest, per_op_med=honest)
    f = lanes._bw_fields(t, nbytes, 3)
    assert f["resolved"] and f["value"] == round(bw(honest), 3)


def test_obs_overhead_lane(accl):
    """The telemetry-overhead lane reports disabled/enabled dispatch
    latency plus the raw disabled-guard cost AND the flight-recorder
    disabled/armed A/B arm (r18), and restores the flags it toggles."""
    from accl_tpu.bench import lanes
    from accl_tpu.obs import flight, metrics

    r = lanes.bench_obs_overhead(accl, count=1 << 10, calls=4, rounds=2)
    assert r["metric"] == "obs_overhead" and r["unit"] == "us"
    assert r["dispatch_disabled_us"] > 0
    assert r["dispatch_enabled_us"] > 0
    assert r["disabled_guard_ns"] >= 0
    assert r["flight_disabled_us"] > 0
    assert r["flight_armed_us"] > 0
    assert isinstance(r["flight_delta_pct"], float)
    assert metrics.ENABLED        # the lane restores the flags
    assert flight.ENABLED


def test_fault_overhead_lane(accl):
    """The round-14 fault-injection overhead lane: interleaved
    disabled/armed-inert send-recv dispatch A/B (the obs_overhead
    shape), raw disabled-guard cost on the record, harness disarmed on
    exit, and the lane name in the bench catalog."""
    from bench import KNOWN_LANES
    from accl_tpu import fault
    from accl_tpu.bench import lanes

    assert "fault_overhead" in KNOWN_LANES
    r = lanes.bench_fault_overhead(accl, count=1 << 8, calls=4, rounds=2)
    assert r["metric"] == "fault_overhead" and r["unit"] == "us"
    assert r["dispatch_disabled_us"] > 0
    assert r["dispatch_enabled_us"] > 0
    assert r["disabled_guard_ns"] >= 0
    assert "enabled_delta_pct" in r
    assert "disabled_guard_pct_of_dispatch" in r
    assert not fault.ENABLED      # the lane disarms the harness


def test_recover_time_lane(accl):
    """The round-15 recovery-cost lane: p50/p99 of ACCL.recover() with
    direction=lower (bench/compare.py inverts), the mode honesty flag
    (local on this rung — no fabric, so the headline is zeroed under the
    resolution protocol), and the configured detection ceiling on the
    record beside the measured cost."""
    from bench import KNOWN_LANES
    from accl_tpu.bench import lanes

    assert "recover_time" in KNOWN_LANES
    r = lanes.bench_recover_time(accl, rounds=2)
    assert r["metric"] == "recover_time" and r["unit"] == "us"
    assert r["direction"] == "lower"
    assert r["mode"] == "local" and r["resolved"] is False
    assert r["value"] == 0.0            # unresolved headline zeroed
    assert r["p50_us"] > 0
    assert r["p99_us"] >= r["p50_us"] >= r["raw_best_us"] > 0
    assert r["detection_bound_s"] == pytest.approx(
        accl.config.heartbeat_timeout_s + accl.config.heartbeat_interval_s)


def test_pp_1f1b_lane_schema(accl):
    """The pipeline schedule A/B lane follows the resolution protocol:
    fused_engaged mirrors the relay engage resolution (False on this
    rung — the 1F1B arm rides the counted ppermute fallback and the
    headline zeroes), both arms' schedules and bubble fractions are
    pinned, the 1F1B stash is O(world) on the record, and raw ratios
    survive either way."""
    from bench import KNOWN_LANES
    from accl_tpu.bench import lanes
    from accl_tpu.ops import pipeline_relay as relay

    assert "pp_1f1b" in KNOWN_LANES
    W = accl.world_size
    rows = lanes.bench_pp_1f1b(accl.global_comm(), n_micro=W,
                               d_model=16, n_rows=4, rounds=2)
    assert [r["metric"] for r in rows] == ["pp_1f1b"]
    r = rows[0]
    assert r["unit"] == "ratio"
    assert r["world"] == W and r["n_micro"] == W
    assert r["schedule"] == "1f1b" and r["schedule_base"] == "gpipe"
    assert r["fused_engaged"] == relay.relay_engages(4, 16, "float32", W)
    assert r["resolved"] == r["fused_engaged"]
    assert r["raw_speedup_med"] > 0
    assert r["onef_us"] > 0 and r["gpipe_us"] > 0
    assert r["stash_slots"] <= W            # the 1F1B memory claim
    assert 0 <= r["bubble_1f1b"] <= r["bubble_gpipe"] <= 1
    if not r["resolved"]:
        assert r["value"] == 0.0
        assert r["relay_reason"] is not None


def test_pp_1f1b_lane_compares(tmp_path):
    """bench/compare.py schema coverage for the pp_1f1b lane: resolved
    rows diff as ratios (a drop flags), unresolved rows stay
    incomparable — the honesty-zeroed headline must never read as a
    100% regression."""
    import json as _json

    from accl_tpu.bench import compare

    base = {"metric": "allreduce_ring_algbw_8dev", "value": 10.0,
            "lanes": [{"metric": "pp_1f1b", "value": 1.5,
                       "resolved": True}]}
    new_bad = {"metric": "allreduce_ring_algbw_8dev", "value": 10.0,
               "lanes": [{"metric": "pp_1f1b", "value": 1.0,
                          "resolved": True}]}
    new_flagged = {"metric": "allreduce_ring_algbw_8dev", "value": 10.0,
                   "lanes": [{"metric": "pp_1f1b", "value": 0.0,
                              "resolved": False}]}
    a = tmp_path / "a.json"
    a.write_text(_json.dumps(base) + "\n")
    out = compare.compare(compare.load_artifact(str(a)), new_bad)
    assert out["regressions"] == ["pp_1f1b"]
    out = compare.compare(compare.load_artifact(str(a)), new_flagged)
    statuses = {r["metric"]: r["status"] for r in out["rows"]}
    assert statuses["pp_1f1b"] == "incomparable"
    assert not out["regressed"]


def test_cmatmul_dw_and_stream_lanes_schema(accl):
    """Round-9 lanes follow the resolution protocol on every rung: the
    dw lane's honesty flag mirrors the wgrad plan + rung, the stream
    lane pins which plan MODE ran (a resident or fallback rung must
    never report a streaming win), and the bf16 wire A/B fields are
    always on the record."""
    from accl_tpu.bench import lanes
    from accl_tpu.ops import collective_matmul as cm

    rows = lanes.bench_cmatmul_dw(accl.global_comm(), m=8, k=32, n=24,
                                  rounds=2)
    assert [r["metric"] for r in rows] == ["cmatmul_dw"]
    r = rows[0]
    assert r["unit"] == "ratio"
    assert r["wgrad_plan"] is not None       # tiny shapes fit VMEM
    assert r["fused_engaged"] == cm._kernels_available()
    assert r["resolved"] == r["fused_engaged"]
    assert r["raw_overlap_eff_med"] > 0
    assert r["fused_us"] > 0 and r["matmul_us"] > 0
    if not r["resolved"]:
        assert r["value"] == 0.0

    rows = lanes.bench_cmatmul_stream(accl.global_comm(), m=16, n=128,
                                      ks=(8192, 16384), rounds=2)
    assert [r["metric"] for r in rows] == ["cmatmul_stream"]
    r = rows[0]
    assert r["unit"] == "ratio"
    assert r["plan_mode"] in ("resident", "stream", None)
    streaming = r["plan_mode"] == "stream"
    assert r["fused_engaged"] == (cm._kernels_available() and streaming)
    assert r["resolved"] == r["fused_engaged"]
    assert r["wire_bytes_ratio"] == 0.5
    assert r["wire_fused_us"] > 0 and r["fused_us"] > 0
    if streaming:
        assert r["k_block"] is not None and r["k_block"] % 128 == 0
    if not r["resolved"]:
        assert r["value"] == 0.0 and r["wire_speedup"] is None


def test_cmatmul_nblock_lane_schema(accl, monkeypatch):
    """The round-20 accumulator-floor lane follows the resolution
    protocol: under a pinched budget the shape n-blocks and the flag
    mirrors rung + register + plan arm; with the register off (or no
    candidate n-blocking, as at the default budget with tiny shapes)
    the lane stays on the record unresolved — never measuring the
    wrong arm under a streaming headline."""
    from accl_tpu.bench import lanes
    from accl_tpu.ops import collective_matmul as cm

    monkeypatch.setattr(cm, "_VMEM_BUDGET", 128 << 10)
    rows = lanes.bench_cmatmul_nblock(
        accl.global_comm(), shapes=((256, 256, 128),), rounds=2)
    assert [r["metric"] for r in rows] == ["cmatmul_nblock"]
    r = rows[0]
    assert r["unit"] == "ratio"
    assert r["plan_mode"] == "stream"
    assert r["m_block"] is not None and r["n_m_blocks"] > 1
    assert r["nblock_enabled"]
    assert r["fused_engaged"] == cm._kernels_available()
    assert r["resolved"] == r["fused_engaged"]
    assert r["raw_overlap_eff_med"] > 0
    assert r["fused_us"] > 0 and r["matmul_us"] > 0
    if not r["resolved"]:
        assert r["value"] == 0.0

    # register off: the plan loses its n-block arm and the lane
    # reports itself unresolved (honest, not a zero-time win)
    saved = cm.get_nblock_enabled()
    cm.set_nblock_enabled(False)
    try:
        rows = lanes.bench_cmatmul_nblock(
            accl.global_comm(), shapes=((256, 256, 128),), rounds=2)
    finally:
        cm.set_nblock_enabled(saved)
    r = rows[0]
    assert not r["nblock_enabled"]
    assert not r["fused_engaged"] and not r["resolved"]
    assert r["value"] == 0.0 and r["m_block"] is None


def test_moe_a2a_dw_lane_schema(accl):
    """The round-20 fused a2a-wgrad lane follows the resolution
    protocol on every rung: the honesty flag needs rung + plan + the
    ``moe_dw_overlap`` register (off is a requested baseline — the
    lane then measures the unfused pair and zeroes its headline)."""
    from accl_tpu.bench import lanes
    from accl_tpu.ops import collective_alltoall as ca
    from accl_tpu.ops import collective_matmul as cm

    rows = lanes.bench_moe_a2a_dw(accl.global_comm(), e_local=2, C=8,
                                  ct=32, cl=48, rounds=2)
    assert [r["metric"] for r in rows] == ["moe_a2a_dw"]
    r = rows[0]
    assert r["unit"] == "ratio"
    assert r["overlap_plan"] is not None     # tiny shapes fit VMEM
    assert r["plan_mode"] == "resident"
    assert r["dw_overlap_enabled"]
    assert r["fused_engaged"] == cm._kernels_available()
    assert r["resolved"] == r["fused_engaged"]
    assert r["raw_overlap_eff_med"] > 0
    assert r["fused_us"] > 0 and r["matmul_us"] > 0
    if not r["resolved"]:
        assert r["value"] == 0.0

    ca.set_dw_overlap_enabled(False)
    try:
        rows = lanes.bench_moe_a2a_dw(accl.global_comm(), e_local=2,
                                      C=8, ct=32, cl=48, rounds=2)
    finally:
        ca.set_dw_overlap_enabled(True)
    r = rows[0]
    assert not r["dw_overlap_enabled"]
    assert not r["fused_engaged"] and not r["resolved"]
    assert r["value"] == 0.0


def test_round20_lanes_in_known_lanes():
    """The round-20 lanes are selectable via --lanes (rows carry no
    ``direction`` tag, so compare treats them as overlap ratios —
    higher is better)."""
    import bench as bench_script

    assert "cmatmul_nblock" in bench_script.KNOWN_LANES
    assert "moe_a2a_dw" in bench_script.KNOWN_LANES


def test_zero_fsdp_lane_schema(accl):
    """The flagship end-to-end lane follows the resolution protocol on
    every rung: the honesty flag mirrors the layerwise engage
    resolution (False here, where the kernels cannot run — the "fused"
    time measures the committed flat fallback), plan modes are pinned,
    raw ratios stay on the record, and an unengaged lane zeroes its
    headline."""
    from accl_tpu.bench import lanes
    from accl_tpu.models import zero

    rows = lanes.bench_zero_fsdp(accl.global_comm(), n_layers=1,
                                 d_model=16, d_hidden=32, n_heads=4,
                                 batch_per_rank=8, rounds=2)
    assert [r["metric"] for r in rows] == ["zero_fsdp"]
    r = rows[0]
    assert r["unit"] == "ratio"
    assert r["world"] == accl.world_size
    assert r["dp"] * r["tp"] == r["world"]
    assert r["fused_engaged"] == zero.fsdp_engages(
        16, 32, 8, r["dp"], r["tp"], overlap=True)
    assert r["resolved"] == r["fused_engaged"]
    assert r["raw_overlap_eff_med"] > 0
    assert r["fused_us"] > 0 and r["flat_us"] > 0
    assert r["plan_mode"] in ("resident", "stream", None)
    # round 20: the attn_fused honesty flag mirrors the attention
    # engage resolution, and the kernel count tiers with it (a tier-2
    # run must never report the fully-fused 12)
    assert r["attn_fused"] == zero.fsdp_attn_engages(
        16, 8, r["dp"], r["tp"], overlap=True)
    assert r["kernels_per_layer"] == (12 if r["attn_fused"] else 6)
    if not r["resolved"]:
        assert r["value"] == 0.0


def test_bench_compare_artifacts(tmp_path):
    """bench/compare.py diffs two artifacts lane by lane: >10% drops
    flag as regressions, honesty-flagged lanes are incomparable (a
    zeroed headline must not read as a 100% regression), added/removed
    lanes are findings, and the CLI exits 1 when anything regressed."""
    import json as _json

    from accl_tpu.bench import compare

    base = {"metric": "allreduce_ring_algbw_8dev", "value": 10.0,
            "lanes": [
                {"metric": "cmatmul_ag", "value": 1.5, "resolved": True},
                {"metric": "zero_fsdp", "value": 1.2, "resolved": True},
                {"metric": "flagged", "value": 0.0, "resolved": False},
                {"metric": "gone", "value": 2.0, "resolved": True}]}
    new = {"metric": "allreduce_ring_algbw_8dev", "value": 9.5,
           "lanes": [
               {"metric": "cmatmul_ag", "value": 1.2, "resolved": True},
               {"metric": "zero_fsdp", "value": 1.5, "resolved": True},
               {"metric": "flagged", "value": 3.0, "resolved": False},
               {"metric": "new_lane", "value": 1.0, "resolved": True}]}
    a = tmp_path / "a.json"
    a.write_text(_json.dumps(base) + "\n")
    b = tmp_path / "b.json"
    # the loader takes the LAST parseable JSON line (streamed logs above)
    b.write_text("not json\n" + _json.dumps({"metric": "stale"})
                 + "\n" + _json.dumps(new) + "\n")
    out = compare.compare(compare.load_artifact(str(a)),
                          compare.load_artifact(str(b)), threshold=0.10)
    statuses = {r["metric"]: r["status"] for r in out["rows"]}
    assert statuses == {
        "allreduce_ring_algbw_8dev": "ok",     # -5% within threshold
        "cmatmul_ag": "regression",            # -20%
        "zero_fsdp": "improvement",            # +25%
        "flagged": "incomparable",             # unresolved on both sides
        "gone": "removed",
        "new_lane": "added",
    }
    assert out["regressions"] == ["cmatmul_ag"]
    assert out["regressed"]
    assert compare.main([str(a), str(b)]) == 1           # CI-gateable
    assert compare.main([str(a), str(a)]) == 0


def test_moe_a2a_lanes_schema(accl):
    """The expert-parallel a2a lanes follow the resolution protocol on
    every rung: honesty flags mirror plan + rung (the bwd lane needs
    BOTH direction plans — its dx rides the dual kernel), plan_mode is
    pinned, raw ratios stay on the record, and an unengaged lane zeroes
    its headline."""
    from accl_tpu.bench import lanes
    from accl_tpu.ops import collective_alltoall as ca
    from accl_tpu.ops import collective_matmul as cm

    rows = lanes.bench_moe_a2a(accl.global_comm(), e_local=2, C=8, d=32,
                               h=48, rounds=2)
    assert [r["metric"] for r in rows] == ["moe_a2a"]
    r = rows[0]
    assert r["unit"] == "ratio"
    assert r["overlap_plan"] is not None     # tiny shapes fit VMEM
    assert r["plan_mode"] == "resident"
    assert r["fused_engaged"] == cm._kernels_available()
    assert r["resolved"] == r["fused_engaged"]
    assert r["raw_overlap_eff_med"] > 0
    assert r["fused_us"] > 0 and r["matmul_us"] > 0
    if not r["resolved"]:
        assert r["value"] == 0.0

    rows = lanes.bench_moe_a2a_bwd(accl.global_comm(), e_local=2, C=8,
                                   d=32, h=48, rounds=2)
    assert [r["metric"] for r in rows] == ["moe_a2a_bwd"]
    r = rows[0]
    assert r["unit"] == "ratio"
    assert r["plan_mode"] == "resident"
    assert r["combine_plan_mode"] == "resident"
    assert r["fused_engaged"] == cm._kernels_available()
    assert r["resolved"] == r["fused_engaged"]
    assert r["raw_overlap_eff_med"] > 0
    if not r["resolved"]:
        assert r["value"] == 0.0


def test_sched_synth_lane_schema(accl):
    """The schedule-synthesis A/B lane follows the resolution protocol:
    one row per bandwidth op, the headline zeroed unless the plan
    resolution would actually dispatch the multi-axis schedule on this
    mesh (here: no declared torus -> resolved False while the raw A/B
    and the cost model's predictions stay on the record)."""
    from accl_tpu.bench import lanes

    comm = accl.global_comm()
    rows = lanes.bench_sched_synth(comm, count=256, rounds=2,
                                   cfg=accl.config)
    assert [r["metric"] for r in rows] == [
        "sched_synth_allreduce", "sched_synth_reduce_scatter",
        "sched_synth_allgather"]
    for r in rows:
        assert r["unit"] == "ratio"
        assert r["mesh_shape"] == [2, 4]      # the explicit-AB fallback
        assert r["topology_declared"] is False
        assert r["resolved"] is False and r["value"] == 0.0
        assert r["raw_speedup_med"] > 0       # raws always on the record
        assert r["flat_ring_us"] > 0 and r["multiaxis_us"] > 0
        assert r["predicted_multiaxis_us"] > 0
        assert r["predicted_flat_ring_us"] > r["predicted_multiaxis_us"]
        # the small allreduce/allgather payloads here sit below the
        # latency tier threshold, so the flat star joins the shapes the
        # plan may resolve (round 13)
        assert r["plan_shape"] in ("xla", "flat", "tree", "ring", "kring",
                                   "multiaxis", "hier")


def test_sched_synth_lane_resolves_on_declared_torus(accl):
    """With the torus declared and a ring-window payload, the lane's
    honesty flag turns on and the headline carries the measured
    speedup."""
    from accl_tpu.bench import lanes

    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4])
    rows = lanes.bench_sched_synth(comm, count=1 << 20, rounds=2, cfg=cfg,
                                   ops=("sched_synth_allreduce",))
    [r] = rows
    assert r["metric"] == "sched_synth_allreduce"
    assert r["topology_declared"] is True
    # default config pipelines at this payload (sched_pipeline_chunks=4);
    # both plan shapes dispatch the multi-axis family, so the lane stays
    # resolved — the pipelined arm itself is bench_sched_pipeline's job
    assert r["plan_shape"] == "pipeline"
    assert r["plan_source"] == "cost_model"
    assert r["resolved"] is True
    assert r["value"] == r["raw_speedup_med"] > 0


def test_dcn_twotier_lane_schema(accl):
    """The DCN two-tier compression A/B lane (ISSUE 15): on this
    single-host rig there is no slice boundary, so the explicit
    factor2d A/B runs with the headline zeroed (AUTO would never
    dispatch what is measured here) while the raw compressed-vs-full
    times, the exact wire-byte ratio and the real resolution stay on
    the record."""
    from accl_tpu.bench import lanes

    comm = accl.global_comm()
    rows = lanes.bench_dcn_twotier(comm, count=256, rounds=2,
                                   cfg=accl.config)
    assert [r["metric"] for r in rows] == [
        "dcn_twotier_allreduce", "dcn_twotier_reduce_scatter",
        "dcn_twotier_allgather"]
    for r in rows:
        assert r["unit"] == "ratio"
        assert r["mesh_shape"] == [2, 4]      # the explicit-AB fallback
        assert r["host_aligned"] is False
        assert r["resolved"] is False and r["value"] == 0.0
        assert r["dcn_wire_dtype"] == "bf16"  # "off" session -> bf16 A/B
        assert r["wire_bytes_ratio"] == 0.5   # f32 -> bf16, a layout fact
        assert r["raw_speedup_med"] > 0       # raws always on the record
        assert r["full_precision_us"] > 0 and r["compressed_us"] > 0
        assert r["best_full_precision_us"] > 0
        assert r["plan_shape"] is not None and r["plan_source"]
    # the lane rides KNOWN_LANES / --lanes like every other stage
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import KNOWN_LANES
    assert "dcn_twotier" in KNOWN_LANES


def test_dcn_twotier_lane_resolves_when_host_aligned(accl, monkeypatch):
    """With a (monkeypatched) slice boundary the honesty flag turns on:
    resolution under the wire register picks the two-tier schedule and
    the headline carries the measured compressed-vs-full speedup."""
    from accl_tpu.bench import lanes

    comm = accl.global_comm()
    monkeypatch.setattr(type(comm), "hosts_shape", lambda self: (2, 4))
    rows = lanes.bench_dcn_twotier(comm, count=1 << 18, rounds=2,
                                   cfg=accl.config,
                                   ops=("dcn_twotier_allreduce",))
    [r] = rows
    assert r["metric"] == "dcn_twotier_allreduce"
    assert r["host_aligned"] is True
    assert r["plan_shape"] == "twotier"
    assert r["plan_source"] == "cost_model"
    assert r["resolved"] is True
    assert r["value"] == r["raw_speedup_med"] > 0


def test_sched_pipeline_lane_schema(accl):
    """The chunked-pipelining A/B lane: undeclared mesh -> headline
    zeroed while the three-way raw A/B (ring / sequential multiaxis /
    pipelined) and the cost model's predictions stay on the record."""
    from accl_tpu.bench import lanes

    comm = accl.global_comm()
    rows = lanes.bench_sched_pipeline(comm, count=256, rounds=2,
                                      cfg=accl.config)
    assert [r["metric"] for r in rows] == [
        "sched_pipeline_allreduce", "sched_pipeline_reduce_scatter",
        "sched_pipeline_allgather"]
    for r in rows:
        assert r["unit"] == "ratio"
        assert r["mesh_shape"] == [2, 4]      # the explicit-AB fallback
        assert r["topology_declared"] is False
        assert r["resolved"] is False and r["value"] == 0.0
        assert r["pipeline_chunks"] >= 2
        assert r["raw_speedup_med"] > 0       # raws always on the record
        assert r["flat_ring_us"] > 0 and r["multiaxis_us"] > 0
        assert r["pipeline_us"] > 0 and r["raw_pipeline_us"] > 0
        assert r["predicted_pipeline_us"] > 0
        assert r["predicted_multiaxis_us"] > 0


def test_sched_pipeline_lane_resolves_on_declared_torus(accl):
    """With the torus declared and a payload where max+startup < sum,
    AUTO resolves the pipelined shape and the lane's honesty flag turns
    on."""
    from accl_tpu.bench import lanes

    comm = accl.global_comm()
    cfg = accl.config.replace(sched_mesh_shape=[2, 4])
    rows = lanes.bench_sched_pipeline(
        comm, count=1 << 20, rounds=2, cfg=cfg,
        ops=("sched_pipeline_allreduce",))
    [r] = rows
    assert r["metric"] == "sched_pipeline_allreduce"
    assert r["topology_declared"] is True
    assert r["plan_shape"] == "pipeline"
    assert r["plan_pipeline_chunks"] == cfg.sched_pipeline_chunks
    assert r["pipeline_chunks"] == cfg.sched_pipeline_chunks
    assert r["resolved"] is True
    assert r["value"] == r["raw_speedup_med"] > 0
    # a chunks=1 session never dispatches the pipelined schedule: the
    # lane keeps measuring (raws on record) but zeroes the headline
    seq = cfg.replace(sched_pipeline_chunks=1)
    rows = lanes.bench_sched_pipeline(
        comm, count=1 << 20, rounds=2, cfg=seq,
        ops=("sched_pipeline_allreduce",))
    [r] = rows
    assert r["plan_shape"] == "multiaxis"
    assert r["resolved"] is False and r["value"] == 0.0
    assert r["raw_speedup_med"] > 0


def test_bench_script_rejects_unknown_lane():
    """Satellite: an unknown --lanes name used to filter to an EMPTY
    run; now the script errors out fast, listing the available lanes
    (rc=2, stub artifact still emitted)."""
    import json as _json
    import os
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
    # one CPU device: the suite's 9-device XLA_FLAGS would make the child
    # a multi-chip rig whose cmatmul lanes run interpreted at full size
    env = dict(os.environ, JAX_PLATFORMS="cpu", ACCL_BENCH_QUICK="1",
               XLA_FLAGS="")
    r = subprocess.run([sys.executable, script, "--lanes",
                        "sweep,definitely_not_a_lane"],
                       timeout=120, capture_output=True, text=True, env=env)
    assert r.returncode == 2
    out = _json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "bench_usage_error"
    assert "definitely_not_a_lane" in out["error"]
    assert "sched_synth" in out["error"]      # the menu is in the message
    assert "obs_schema" in out                # stub keeps the artifact keys
    # a valid prefix pattern still passes validation (the filter grammar)
    from bench import KNOWN_LANES
    assert "sched_synth" in KNOWN_LANES


def test_compare_loads_driver_wrapper_artifacts(tmp_path):
    """load_artifact reads all three artifact shapes in the wild: the
    raw one-line artifact, a captured stream, and the driver wrapper
    whose `parsed`/`tail` fields hold the real artifact (the
    BENCH_rNN.json files the repo's rounds actually produce) — the
    shape tools/ci_gate.sh diffs."""
    import json as _json

    from accl_tpu.bench import compare

    art = {"metric": "m", "value": 1.0}
    raw = tmp_path / "raw.json"
    raw.write_text(_json.dumps(art) + "\n")
    assert compare.load_artifact(str(raw))["metric"] == "m"

    wrapped = tmp_path / "wrap.json"
    wrapped.write_text(_json.dumps(
        {"n": 1, "cmd": "bench", "rc": 0, "tail": "log line\n"
         + _json.dumps(art) + "\n", "parsed": None}, indent=1))
    assert compare.load_artifact(str(wrapped))["value"] == 1.0

    parsed = tmp_path / "parsed.json"
    parsed.write_text(_json.dumps(
        {"n": 1, "rc": 0, "tail": "no artifact here", "parsed": art},
        indent=1))
    assert compare.load_artifact(str(parsed))["value"] == 1.0

    crashed = tmp_path / "crashed.json"
    crashed.write_text(_json.dumps(
        {"n": 1, "rc": 1, "tail": "Traceback ...", "parsed": None}))
    with pytest.raises(ValueError, match="crashed round"):
        compare.load_artifact(str(crashed))


def test_flash_decode_lane_schema():
    """Round-13 latency lane protocol: dense + GQA rows report p50/p99
    in µs with direction=lower, honesty flags pin the kernel that ran
    (paged plan admitted, but fused_engaged False off-silicon — the
    timing measures the interpreter), raws stay on the record, and an
    unresolved lane zeroes its headline."""
    from accl_tpu.bench import lanes

    rows = lanes.bench_flash_decode(B=2, H=4, d=128, page=8,
                                    pages_max=2, rounds=2)
    assert [r["metric"] for r in rows] == ["flash_decode_dense",
                                          "flash_decode_gqa"]
    for r in rows:
        assert r["unit"] == "us" and r["direction"] == "lower"
        assert r["plan_mode"] == "paged"      # tiny shape fits the plan
        assert r["plan_reason"] == "ok"
        assert r["fused_engaged"] is False    # no TPU backend here
        assert r["resolved"] == r["fused_engaged"]
        assert r["value"] == 0.0              # unresolved -> zeroed
        assert r["p50_us"] > 0 and r["p99_us"] >= r["p50_us"]
        assert r["raw_best_us"] > 0 and r["raw_worst_us"] >= r["p50_us"]
    assert rows[0]["H_kv"] == 4 and rows[1]["H_kv"] == 1


def test_coll_latency_lane_schema(accl):
    """The small-message collective latency lane: resolved only when
    the latency tier OWNS the decision (source=latency_tier); a
    disabled tier reports its raw A/B with a zeroed headline; both
    sides' p50/p99 and the speedup ratios are always on the record."""
    from accl_tpu.bench import lanes

    comm = accl.global_comm()
    rows = lanes.bench_coll_latency(comm, cfg=accl.config, nbytes=1024,
                                    rounds=2)
    assert [r["metric"] for r in rows] == ["coll_latency_allreduce"]
    r = rows[0]
    assert r["unit"] == "us" and r["direction"] == "lower"
    assert r["plan_source"] == "latency_tier"
    assert r["plan_shape"] == "flat"          # 8-rank α-dominated pick
    assert r["resolved"] is True
    assert r["value"] == r["p50_us"] > 0
    assert r["p99_us"] >= r["p50_us"]
    assert r["xla_p50_us"] > 0 and r["xla_p99_us"] > 0
    assert r["speedup_p50"] is not None

    off = accl.config.replace(latency_tier_threshold=0)
    [r] = lanes.bench_coll_latency(comm, cfg=off, nbytes=1024, rounds=2)
    assert r["plan_source"] == "legacy" and r["resolved"] is False
    assert r["value"] == 0.0 and r["p50_us"] > 0   # raws survive


def test_bench_compare_latency_direction(tmp_path):
    """Satellite (ISSUE 8): lower-is-better lanes invert the regression
    polarity — p99 UP 20% is the regression, DOWN 20% the improvement —
    while untagged lanes keep the historical higher-is-better rule, and
    the CLI exit-code contract (tools/ci_gate.sh) is unchanged."""
    import json as _json

    from accl_tpu.bench import compare

    def art(lat_val, bw_val):
        return {"metric": "allreduce_ring_algbw_8dev", "value": 10.0,
                "lanes": [
                    {"metric": "coll_latency_allreduce", "value": lat_val,
                     "resolved": True, "direction": "lower"},
                    {"metric": "cmatmul_ag", "value": bw_val,
                     "resolved": True}]}

    base = art(100.0, 1.5)
    # latency UP 20% -> regression (pre-fix this read as "improvement")
    out = compare.compare(base, art(120.0, 1.5))
    st = {r["metric"]: r for r in out["rows"]}
    assert st["coll_latency_allreduce"]["status"] == "regression"
    assert st["coll_latency_allreduce"]["direction"] == "lower"
    assert out["regressions"] == ["coll_latency_allreduce"]
    # latency DOWN 20% -> improvement, not a regression
    out = compare.compare(base, art(80.0, 1.5))
    st = {r["metric"]: r["status"] for r in out["rows"]}
    assert st["coll_latency_allreduce"] == "improvement"
    assert not out["regressed"]
    # higher-is-better lanes keep their polarity beside the tagged one
    out = compare.compare(base, art(100.0, 1.0))
    st = {r["metric"]: r["status"] for r in out["rows"]}
    assert st["cmatmul_ag"] == "regression"
    assert st["coll_latency_allreduce"] == "ok"
    # a direction tag present on only ONE side still inverts (a round
    # that ADDED the tag must not flip the comparison's meaning)
    untagged = art(100.0, 1.5)
    del untagged["lanes"][0]["direction"]
    out = compare.compare(untagged, art(120.0, 1.5))
    st = {r["metric"]: r["status"] for r in out["rows"]}
    assert st["coll_latency_allreduce"] == "regression"
    # CLI exit codes: 1 on regression, 0 clean (the ci_gate contract)
    a = tmp_path / "a.json"
    a.write_text(_json.dumps(base) + "\n")
    b = tmp_path / "b.json"
    b.write_text(_json.dumps(art(120.0, 1.5)) + "\n")
    assert compare.main([str(a), str(b)]) == 1
    assert compare.main([str(a), str(a)]) == 0


def test_latency_lanes_in_known_lanes():
    """bench.py --lanes accepts the round-13 lanes."""
    from bench import KNOWN_LANES
    assert "flash_decode" in KNOWN_LANES
    assert "coll_latency" in KNOWN_LANES


def test_compare_flags_calibration_drift():
    """Satellite: a lane carrying predicted_<x>_us beside its measured
    <x>_us gets a calibration warning when they disagree by >3x — an
    advisory for the α-β/startup fit, NEVER a regression exit."""
    from accl_tpu.bench import compare as cmp

    def artifact(pred):
        return {"metric": "bench", "value": 1.0, "lanes": [{
            "metric": "sched_pipeline_allreduce", "unit": "ratio",
            "value": 1.2, "resolved": True,
            "pipeline_us": 100.0, "predicted_pipeline_us": pred,
            "multiaxis_us": 150.0, "predicted_multiaxis_us": 140.0,
        }]}

    ok = cmp.compare(artifact(90.0), artifact(90.0))
    assert ok["calibration_warnings"] == []
    assert not ok["regressed"]
    drifted = cmp.compare(artifact(90.0), artifact(10.0))
    [w] = drifted["calibration_warnings"]
    assert w["metric"] == "sched_pipeline_allreduce"
    assert w["field"] == "pipeline_us"
    assert w["ratio"] == 10.0
    assert "autotune" in w["note"]
    assert not drifted["regressed"]       # advisory only
    # both polarities drift (prediction 3x too high as well)
    high = cmp.compare(artifact(90.0), artifact(400.0))
    assert len(high["calibration_warnings"]) == 1
    # unresolved/errored rows cannot indict the model
    bad = artifact(10.0)
    bad["lanes"][0]["error"] = "boom"
    assert cmp.compare(artifact(90.0), bad)["calibration_warnings"] == []


def test_prefill_chunk_lane_schema():
    """Round-18 serving lane: the chunked-prefill latency row follows
    the flash_decode protocol (direction=lower, honesty flags, zeroed
    headline off-silicon) and carries the token-loop A/B."""
    from accl_tpu.bench import lanes

    [r] = lanes.bench_prefill_chunk(H=4, hkv=2, page=8, pages_max=2,
                                    chunk=16, rounds=2)
    assert r["metric"] == "prefill_chunk"
    assert r["unit"] == "us" and r["direction"] == "lower"
    assert r["plan_mode"] == "paged" and r["plan_reason"] == "ok"
    assert r["fused_engaged"] is False        # no TPU backend here
    assert r["resolved"] is False and r["value"] == 0.0
    assert r["p50_us"] > 0 and r["p99_us"] >= r["p50_us"]
    assert r["loop_p50_us"] > 0 and r["speedup_p50"] is not None
    assert r["chunk"] == 16 and r["tokens_per_s"] > 0
    assert r["prefill_plan"]["chunk"] == 16


def test_decode_spec_lane_schema():
    """Round-18 serving lane: tokens-accepted/s headline (higher-
    better, the default compare polarity — no direction tag), the
    k-sequential A/B on record, honesty-zeroed off-silicon."""
    from accl_tpu.bench import lanes

    [r] = lanes.bench_decode_spec(B=2, H=4, hkv=2, page=8, pages_max=2,
                                  k=2, rounds=2)
    assert r["metric"] == "decode_spec"
    assert r["unit"] == "tokens/s" and "direction" not in r
    assert r["plan_mode"] == "paged" and r["plan_reason"] == "ok"
    assert r["fused_engaged"] is False and r["resolved"] is False
    assert r["value"] == 0.0 and r["tokens_per_s"] > 0
    assert r["p50_us"] > 0 and r["seq_p50_us"] > 0
    assert r["speedup_p50"] is not None and r["k"] == 2


def test_kv_quant_lane_schema():
    """Round-18 serving lane: the bytes/slot reduction headline is an
    exact layout fact (resolved when the quantized plan admits — int8
    vs the bf16 baseline is 2x by construction); the latency A/B rides
    beside it gated by its own timing_engaged flag."""
    from accl_tpu.bench import lanes

    [r] = lanes.bench_kv_quant(B=2, H=4, hkv=2, page=32, pages_max=2,
                               rounds=2)
    assert r["metric"] == "kv_quant_int8"
    assert r["kv_cache_dtype"] == "int8" and r["plan_reason"] == "ok"
    assert r["resolved"] is True
    assert r["value"] == r["kv_bytes_ratio"] == 2.0
    assert r["kv_bytes_per_slot_base"] == 2 * r["kv_bytes_per_slot"]
    assert r["timing_engaged"] is False       # CPU rung times itself
    assert r["p50_us"] > 0 and r["base_p50_us"] > 0
    assert 0 < r["max_err_vs_base"] < 0.1     # codec tolerance, nonzero
    assert r["quant_scale"] == 32.0


def test_serve_disagg_lane_schema():
    """Disaggregated-serving lane: the decode row follows the latency
    protocol (direction=lower, headline zeroed off-silicon) and carries
    the colocated A/B; the handoff row's resolved gates on the
    bit-exactness fact (the kv_quant pattern) with the engaged framing
    on record."""
    from accl_tpu.bench import lanes

    rows = lanes.bench_serve_disagg(prefill_len=32, rounds=2)
    by = {r["metric"]: r for r in rows}
    d = by["serve_disagg_decode"]
    assert d["unit"] == "us" and d["direction"] == "lower"
    assert d["timing_engaged"] is False       # no TPU backend here
    assert d["resolved"] is False and d["value"] == 0.0
    assert d["p50_us"] > 0 and d["colo_p50_us"] > 0
    assert d["p99_colo_over_disagg"] > 0
    assert d["tokens_per_s"] > 0 and d["kv_cache_dtype"] == "int8"
    h = by["serve_disagg_handoff"]
    assert h["unit"] == "us" and h["direction"] == "lower"
    assert h["bit_exact"] is True and h["resolved"] is True
    assert h["value"] == h["p50_us"] > 0
    assert h["page_batch_engaged"] is True
    assert h["handoff_bytes"] > 0 and h["used_pages"] == 1
    assert h["timing_engaged"] is False


def test_serve_disagg_lane_needs_three_devices(monkeypatch):
    """Fleet honesty: on a rig with fewer than 3 devices the lane emits
    skipped stubs instead of half-running the A/B."""
    from accl_tpu.bench import lanes

    monkeypatch.setattr(lanes.jax, "devices", lambda *a, **k: [object()])
    rows = lanes.bench_serve_disagg()
    assert all(r["skipped"] and not r["resolved"] for r in rows)
    assert {r["metric"] for r in rows} == {"serve_disagg_decode",
                                           "serve_disagg_handoff"}


def test_serving_lanes_in_known_lanes_and_compare():
    """bench.py --lanes accepts the round-18 lanes, and compare.py
    applies the right polarity to each: prefill_chunk inverts
    (direction=lower), decode_spec and kv_quant keep higher-better."""
    from bench import KNOWN_LANES
    from accl_tpu.bench import compare

    for name in ("prefill_chunk", "decode_spec", "kv_quant",
                 "serve_disagg"):
        assert name in KNOWN_LANES

    def art(pre, spec, quant):
        return {"metric": "m", "value": 1.0, "lanes": [
            {"metric": "prefill_chunk", "value": pre,
             "resolved": True, "direction": "lower"},
            {"metric": "decode_spec", "value": spec, "resolved": True},
            {"metric": "kv_quant_int8", "value": quant,
             "resolved": True}]}

    names = ("prefill_chunk", "decode_spec", "kv_quant_int8")
    base = art(100.0, 5000.0, 2.0)
    out = compare.compare(base, art(130.0, 4000.0, 1.0))
    st = {r["metric"]: r["status"] for r in out["rows"]}
    assert all(st[n] == "regression" for n in names)
    out = compare.compare(base, art(80.0, 6000.0, 4.0))
    st = {r["metric"]: r["status"] for r in out["rows"]}
    assert all(st[n] == "improvement" for n in names)
    assert not out["regressed"]


def test_weights_publish_lane_schema(accl):
    """The weight-publication lane follows the latency-lane protocol on
    every rung: direction=lower µs headline, fused-vs-host-gather A/B
    fields always on record, the honesty flag mirroring the publish
    engage resolution, and the synth route + wire-byte ratio pinned."""
    from accl_tpu.bench import lanes
    from accl_tpu.models import publish

    rows = lanes.bench_weights_publish(accl.global_comm(),
                                       cfg=accl.config, n_layers=1,
                                       d_model=16, n_heads=4, rounds=2)
    assert [r["metric"] for r in rows] == ["weights_publish"]
    r = rows[0]
    assert r["unit"] == "us" and r["direction"] == "lower"
    assert r["world"] == accl.world_size
    assert r["dp"] * r["tp"] == r["world"]
    assert r["fused_engaged"] == publish.publish_engages(
        16, 4, r["dp"], r["tp"])
    assert r["resolved"] == r["fused_engaged"]
    assert r["p50_us"] > 0 and r["p99_us"] >= r["p50_us"] > 0 \
        or r["p99_us"] >= 0
    assert r["host_p50_us"] > 0 and r["host_over_fused"] > 0
    assert r["publish_bytes"] == publish.publication_bytes(1, 16)
    assert r["wire_dtype"] == (accl.config.dcn_wire_dtype or "off")
    if r["wire_dtype"] == "off":
        assert r["wire_bytes_ratio"] == 1.0
    assert r["plan_source"] in ("legacy", "cost_model", "latency_tier",
                                "override", "full_authority")
    assert r["plan_shape"] is not None
    if not r["resolved"]:
        assert r["value"] == 0.0
        assert r["engage_reason"] is not None


def test_weights_publish_in_known_lanes_and_compare():
    """bench.py --lanes accepts the publish lane, and compare.py
    applies the LOWER-is-better polarity: a publication latency going
    up is the regression, an honesty-zeroed row stays incomparable."""
    from bench import KNOWN_LANES
    from accl_tpu.bench import compare

    assert "weights_publish" in KNOWN_LANES

    def art(v, resolved=True):
        return {"metric": "m", "value": 1.0, "lanes": [
            {"metric": "weights_publish", "value": v,
             "resolved": resolved, "direction": "lower"}]}

    base = art(100.0)
    out = compare.compare(base, art(130.0))
    assert out["regressions"] == ["weights_publish"]
    out = compare.compare(base, art(80.0))
    st = {r["metric"]: r["status"] for r in out["rows"]}
    assert st["weights_publish"] == "improvement"
    out = compare.compare(base, art(0.0, resolved=False))
    st = {r["metric"]: r["status"] for r in out["rows"]}
    assert st["weights_publish"] == "incomparable"
    assert not out["regressed"]


def test_autotune_publish_gates(accl):
    """autotune_publish is ICI-gated (the emulator rung passes the
    config through untouched) and rides autotune_session's stage list —
    the go/no-go writes cfg.publish_fused only where the fused program
    can actually be measured."""
    import inspect

    from accl_tpu.bench import autotune

    cfg = autotune.autotune_publish(accl, accl.config, reps=1)
    assert cfg.publish_fused == accl.config.publish_fused
    src = inspect.getsource(autotune.autotune_session)
    assert "autotune_publish" in src


def test_peak_table_refuses_unknown_device_kind(monkeypatch):
    """A device kind without a peak on record is an error, not a default
    (the anti-cheat floor and MFU denominators read this table)."""
    from accl_tpu.bench import harness

    class _Dev:
        device_kind = "TPU v99 prototype"

    monkeypatch.setattr(harness.jax, "devices", lambda: [_Dev()])
    with pytest.raises(ValueError, match="no peak on record"):
        harness.hbm_peak_bytes_per_s()
    _Dev.device_kind = "TPU v5 lite"
    assert harness.hbm_peak_bytes_per_s() == pytest.approx(819e9)
    assert np.isfinite(harness.bf16_peak_flops())
