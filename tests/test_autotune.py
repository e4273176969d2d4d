"""Adaptive tuning registers: measured crossover thresholds replace the
static defaults, and AUTO selection honors them."""
import numpy as np
import pytest

import jax

import accl_tpu
from accl_tpu import Algorithm, dataType, reduceFunction
from accl_tpu.bench import autotune
from accl_tpu.constants import operation
from accl_tpu.parallel import algorithms

WORLD = 8


def test_crossover_logic():
    counts = [16, 64, 256, 1024]
    # candidate wins from index 2 on
    base = [1.0, 1.0, 1.0, 1.0]
    cand = [2.0, 1.5, 0.5, 0.4]
    assert autotune._crossover(counts, base, cand, 4) == 256 * 4
    # never wins
    assert autotune._crossover(counts, base, [3.0] * 4, 4) is None
    # wins early then loses -> crossover is where it stays ahead
    assert autotune._crossover(counts, base, [0.5, 2.0, 0.4, 0.4], 4) \
        == 256 * 4


def test_autotune_produces_honored_config(accl):
    tuned = autotune.autotune_allreduce(accl, pows=(6, 9), reps=1)
    assert tuned.ring_threshold > 0
    assert tuned.hier_threshold > 0
    # the tuned config changes AUTO selection consistently with the values
    comm = accl.global_comm()
    below = tuned.ring_threshold - 4
    at = tuned.ring_threshold
    if below > tuned.max_eager_size:  # stay out of the rendezvous regime
        assert algorithms.select(operation.allreduce, below, comm, tuned) \
            != Algorithm.RING or below >= tuned.ring_threshold
    if at < tuned.hier_threshold:
        assert algorithms.select(operation.allreduce, at, comm, tuned) \
            == Algorithm.RING


def test_accl_autotune_applies_and_clears_cache(accl, rng):
    orig = accl.config
    try:
        accl.autotune(pows=(6, 9), reps=1)
        assert accl.config.ring_threshold != 0
        # collectives still correct with the tuned config in place
        s = accl.create_buffer(64, dataType.int32)
        r = accl.create_buffer(64, dataType.int32)
        s.host[:] = rng.integers(-50, 50, (WORLD, 64)).astype(np.int32)
        accl.allreduce(s, r, 64, reduceFunction.SUM)
        np.testing.assert_array_equal(
            r.host, np.tile(s.host.sum(0), (WORLD, 1)))
    finally:
        accl.config = orig


def test_autotune_session_covers_every_knob(accl):
    """Round-3 (VERDICT r2 #7): autotune writes every threshold select()
    reads — allgather/reduce_scatter ring crossovers and the flat-tree
    rank/count/fan-in registers, not just the allreduce pair."""
    tuned = autotune.autotune_session(accl, pows=(6, 9), reps=1)
    touched = {
        "ring_threshold", "ag_ring_threshold", "rs_ring_threshold",
        "bcast_flat_tree_max_ranks", "reduce_flat_tree_max_ranks",
        "reduce_flat_tree_max_count", "gather_flat_tree_max_fanin",
    }
    for name in touched:
        assert getattr(tuned, name) is not None
    # rank maxima resolve as go/no-go at the live world size
    assert tuned.bcast_flat_tree_max_ranks in (WORLD, WORLD - 1)
    assert tuned.reduce_flat_tree_max_ranks in (WORLD, WORLD - 1)
    assert tuned.gather_flat_tree_max_fanin in (2, 4, WORLD)
    # tuned values are consumed by selection without error
    comm = accl.global_comm()
    for nbytes in (1024, 1 << 22, 1 << 27):
        algorithms.select(operation.allgather, nbytes, comm, tuned)
        algorithms.select(operation.reduce_scatter, nbytes, comm, tuned)
        algorithms.select(operation.reduce, nbytes, comm, tuned, count=64)


def test_autotune_round20_registers_on_ici(accl, monkeypatch):
    """The round-20 go/no-go stages write their registers from the
    measured A/B on ICI — ``cmatmul_nblock`` from the n-block arm vs
    the unfused pair, ``moe_dw_overlap`` from the fused a2a-wgrad vs
    its pair — and pass the config through untouched when the geometry
    never reaches the arm (engage-gated, like autotune_zero_fsdp)."""
    from accl_tpu.config import TransportBackend

    calls = {"n": 0}
    fused_wins = {"v": True}

    def fake_time(prog, *args, reps):
        # each stage times fused first, baseline second
        calls["n"] += 1
        first = calls["n"] % 2 == 1
        return 1.0 if first == fused_wins["v"] else 2.0

    monkeypatch.setattr(autotune, "_time_prog", fake_time)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        tuned = autotune.autotune_cmatmul_nblock(accl, accl.config, reps=1)
        assert tuned.cmatmul_nblock is True
        tuned = autotune.autotune_moe_a2a_dw(accl, accl.config, reps=1)
        assert tuned.moe_dw_overlap is True

        fused_wins["v"] = False
        tuned = autotune.autotune_cmatmul_nblock(accl, accl.config, reps=1)
        assert tuned.cmatmul_nblock is False
        tuned = autotune.autotune_moe_a2a_dw(accl, accl.config, reps=1)
        assert tuned.moe_dw_overlap is False

        # a geometry that stays resident never reaches the n-block arm:
        # the stage must pass the config through untouched rather than
        # writing a register from the wrong measurement
        base = accl.config.replace(cmatmul_nblock=True)
        calls_before = calls["n"]
        tuned = autotune.autotune_cmatmul_nblock(accl, base, m=16, k=32,
                                                 n=32, reps=1)
        assert tuned is base and calls["n"] == calls_before
    finally:
        accl.config = orig


def test_tuned_config_changes_selection(accl, monkeypatch):
    """Deterministic: synthetic timings where RING wins from 2^9 elements
    on flip the allgather selection relative to the defaults."""
    counts = [2 ** 6, 2 ** 9]

    def fake_measure(comm, cs, algos, dt, reps, bidirectional=False):
        assert list(cs) == counts
        return {Algorithm.XLA: [1.0, 1.0],
                Algorithm.RING: [2.0, 0.5]}  # wins from index 1 on

    monkeypatch.setattr(autotune, "measure_allgather", fake_measure)
    tuned = autotune.autotune_allgather(accl, accl.config, pows=(6, 9),
                                        reps=1)
    assert tuned.ag_ring_threshold == 2 ** 9 * 4
    comm = accl.global_comm()
    got = algorithms.select(operation.allgather, 2 ** 9 * 4, comm, tuned)
    assert got == Algorithm.RING
    # default config at the same size picks XLA (threshold 4 MiB)
    assert algorithms.select(
        operation.allgather, 2 ** 9 * 4, comm, accl.config) == Algorithm.XLA


def test_autotune_pallas_crossover_on_ici(accl, monkeypatch):
    """On an ICI transport the PALLAS family joins the allreduce
    measurement and its crossover lands in pallas_threshold."""
    from accl_tpu.config import TransportBackend
    counts = [2 ** 6, 2 ** 9]

    def fake_measure(comm, cs, algos, dt, reps, bidirectional=False):
        assert Algorithm.PALLAS in algos
        t = {a: [1.0, 1.0] for a in algos}
        t[Algorithm.RING] = [3.0, 3.0]
        t[Algorithm.PALLAS] = [2.0, 0.25]  # wins from index 1 on
        return t

    monkeypatch.setattr(autotune, "measure_allreduce", fake_measure)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        tuned = autotune.autotune_allreduce(accl, pows=(6, 9), reps=1)
        assert tuned.pallas_threshold == 2 ** 9 * 4
        comm = accl.global_comm()
        assert algorithms.select(
            operation.allreduce, 2 ** 9 * 4, comm, tuned) == Algorithm.PALLAS
    finally:
        accl.config = orig


def test_autotune_bcast_pallas_crossover_on_ici(accl, monkeypatch):
    """The pipelined-ring Pallas bcast joins the tuned set on ICI: its
    measured crossover vs the best jnp family lands in
    bcast_pallas_threshold (and select() then engages it)."""
    from accl_tpu.config import TransportBackend

    def fake_measure(comm, cs, algos, dt, reps, segment_bytes=None):
        assert Algorithm.PALLAS in algos and Algorithm.TREE in algos
        t = {a: [1.0, 1.0] for a in algos}
        t[Algorithm.TREE] = [0.5, 1.5]      # best-of includes TREE at idx 0
        t[Algorithm.PALLAS] = [0.75, 0.25]  # wins from index 1 on
        return t

    monkeypatch.setattr(autotune, "measure_bcast", fake_measure)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        tuned = autotune.autotune_bcast(accl, accl.config, pows=(6, 9),
                                        reps=1)
        assert tuned.bcast_pallas_threshold == 2 ** 9 * 4
        comm = accl.global_comm()
        assert algorithms.select(
            operation.bcast, 2 ** 9 * 4, comm, tuned) == Algorithm.PALLAS
        # off ICI the knob is untouched
        accl.config = orig
        same = autotune.autotune_bcast(accl, accl.config, pows=(6, 9),
                                       reps=1)
        assert same.bcast_pallas_threshold == orig.bcast_pallas_threshold
    finally:
        accl.config = orig


def test_autotune_gather_pallas_crossover_on_ici(accl, monkeypatch):
    """The ring-relay Pallas gather joins the tuned set on ICI: its
    crossover vs the best jnp family lands in gather_pallas_threshold."""
    from accl_tpu.config import TransportBackend

    def fake_measure(comm, cs, algos, dt, reps, segment_bytes=None):
        assert Algorithm.PALLAS in algos and Algorithm.RING in algos
        t = {a: [1.0, 1.0] for a in algos}
        t[Algorithm.PALLAS] = [2.0, 0.5]  # wins from index 1 on
        return t

    monkeypatch.setattr(autotune, "measure_gather", fake_measure)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        tuned = autotune.autotune_gather(accl, accl.config, pows=(6, 9),
                                         reps=1)
        assert tuned.gather_pallas_threshold == 2 ** 9 * 4
        comm = accl.global_comm()
        assert algorithms.select(
            operation.gather, 2 ** 9 * 4, comm, tuned) == Algorithm.PALLAS
    finally:
        accl.config = orig


def test_autotune_scatter_pallas_crossover_on_ici(accl, monkeypatch):
    """The ring-relay Pallas scatter joins the tuned set on ICI: its
    crossover vs the best jnp family lands in scatter_pallas_threshold."""
    from accl_tpu.config import TransportBackend

    def fake_measure(comm, cs, algos, dt, reps, segment_bytes=None):
        assert Algorithm.PALLAS in algos and Algorithm.FLAT in algos
        t = {a: [1.0, 1.0] for a in algos}
        t[Algorithm.PALLAS] = [2.0, 0.5]  # wins from index 1 on
        return t

    monkeypatch.setattr(autotune, "measure_scatter", fake_measure)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        tuned = autotune.autotune_scatter(accl, accl.config, pows=(6, 9),
                                          reps=1)
        assert tuned.scatter_pallas_threshold == 2 ** 9 * 4
        comm = accl.global_comm()
        assert algorithms.select(
            operation.scatter, 2 ** 9 * 4, comm, tuned) == Algorithm.PALLAS
    finally:
        accl.config = orig


def test_config_save_load_roundtrip(tmp_path):
    """ACCLConfig persists as JSON (atomically) and loads back identical
    — the durable tuning-register analog (accl.cpp:1214-1224 re-writes
    per bring-up; we measure once and reload). A file whose schema does
    not match EXACTLY (extra OR missing keys — a cache from a different
    version) fails loudly instead of half-applying."""
    from accl_tpu.config import ACCLConfig, Algorithm, TransportBackend
    cfg = ACCLConfig().replace(
        ring_threshold=12345, algorithm=Algorithm.RING,
        transport=TransportBackend.ICI, gather_flat_tree_max_fanin=3)
    path = str(tmp_path / "tuned.json")
    cfg.save(path)
    back = ACCLConfig.load(path)
    assert back == cfg
    import json
    d = json.load(open(path))
    d["no_such_knob"] = 1
    json.dump(d, open(path, "w"))
    with pytest.raises(ValueError, match="no_such_knob"):
        ACCLConfig.load(path)
    d.pop("no_such_knob")
    d.pop("ring_threshold")  # older version missing a field: also loud
    json.dump(d, open(path, "w"))
    with pytest.raises(ValueError, match="ring_threshold"):
        ACCLConfig.load(path)
    # fingerprint pins the deployment the tuning belongs to
    cfg.save(path, fingerprint={"world": 8})
    assert ACCLConfig.load(path, expect_fingerprint={"world": 8}) == cfg
    with pytest.raises(ValueError, match="fingerprint"):
        ACCLConfig.load(path, expect_fingerprint={"world": 16})


def test_autotune_cache_path(accl, monkeypatch, tmp_path):
    """autotune(cache_path=...) measures once and saves; a second session
    loads the file instead of re-measuring. An unusable cache — crash-
    truncated JSON or one fingerprinted for a different deployment —
    falls back to measuring and overwrites, never bricking bring-up."""
    from accl_tpu.config import ACCLConfig
    calls = []

    def fake_session(acc, **kw):
        calls.append(1)
        return acc.config.replace(ring_threshold=777)

    monkeypatch.setattr(autotune, "autotune_session", fake_session)
    path = str(tmp_path / "tuned.json")
    orig = accl.config
    try:
        accl.autotune(cache_path=path)
        assert accl.config.ring_threshold == 777 and len(calls) == 1
        accl.config = orig
        accl.autotune(cache_path=path)  # loads, does not re-measure
        assert accl.config.ring_threshold == 777 and len(calls) == 1

        # truncated file (crash mid-write of a non-atomic writer)
        with open(path, "w") as f:
            f.write('{"ring_thresh')
        accl.config = orig
        accl.autotune(cache_path=path)
        assert accl.config.ring_threshold == 777 and len(calls) == 2
        # ...and the fallback rewrote a valid cache
        accl.config = orig
        accl.autotune(cache_path=path)
        assert len(calls) == 2

        # cache tuned on a different deployment (wrong fingerprint)
        accl.config.save(path, fingerprint={"world": 99, "transport": "x",
                                            "schema": 1})
        accl.config = orig
        accl.autotune(cache_path=path)
        assert len(calls) == 3  # re-measured, not silently adopted
    finally:
        accl.config = orig


def test_autotune_reduce_pallas_crossover_on_ici(accl, monkeypatch):
    """The chunked RS + relay-gather Pallas reduce joins the tuned set."""
    from accl_tpu.config import TransportBackend

    def fake_measure(comm, cs, algos, dt, reps, segment_bytes=None):
        assert Algorithm.PALLAS in algos and Algorithm.TREE in algos
        t = {a: [1.0, 1.0] for a in algos}
        t[Algorithm.PALLAS] = [2.0, 0.5]  # wins from index 1 on
        return t

    monkeypatch.setattr(autotune, "measure_reduce", fake_measure)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        tuned = autotune.autotune_reduce(accl, accl.config, pows=(6, 9),
                                         reps=1)
        assert tuned.reduce_pallas_threshold == 2 ** 9 * 4
        comm = accl.global_comm()
        assert algorithms.select(
            operation.reduce, 2 ** 9 * 4, comm, tuned) == Algorithm.PALLAS
    finally:
        accl.config = orig


def test_autotune_alltoall_pallas_crossover_on_ici(accl, monkeypatch):
    """The phased-rotation Pallas alltoall joins the tuned set on ICI."""
    from accl_tpu.config import TransportBackend

    def fake_measure(comm, cs, algos, dt, reps, segment_bytes=None):
        assert Algorithm.PALLAS in algos and Algorithm.FLAT in algos
        t = {a: [1.0, 1.0] for a in algos}
        t[Algorithm.PALLAS] = [2.0, 0.5]  # wins from index 1 on
        return t

    monkeypatch.setattr(autotune, "measure_alltoall", fake_measure)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        tuned = autotune.autotune_alltoall(accl, accl.config, pows=(6, 9),
                                           reps=1)
        assert tuned.alltoall_pallas_threshold == 2 ** 9 * 4
        comm = accl.global_comm()
        assert algorithms.select(
            operation.alltoall, 2 ** 9 * 4, comm, tuned) == Algorithm.PALLAS
    finally:
        accl.config = orig


def test_autotune_collective_matmul_crossover_on_ici(accl, monkeypatch):
    """The overlap crossovers land in ag/rs_matmul_threshold on ICI —
    and the sweep NEVER includes sizes whose overlap plan misses the
    VMEM budget (there the 'PALLAS' builder silently runs the XLA
    fallback, and the crossover would time XLA against itself and
    write DISABLED on a healthy mesh — the review-r7 finding)."""
    from accl_tpu.config import TransportBackend
    from accl_tpu.ops import collective_matmul as cm

    seen = {}

    def fake_measure(comm, ms, algos, k=512, n=512, dt=None, reps=1,
                     bidirectional=True, ops=("agmm", "mmrs"),
                     wire_dtype=None):
        seen[ops[0]] = list(ms)
        # every requested size must have a live overlap plan
        for m in ms:
            if "agmm" in ops:
                assert cm.agmm_plan(m, k, n, comm.world_size,
                                    np.float32, bidirectional) is not None
            if "mmrs" in ops:
                assert cm.mmrs_plan(comm.world_size * m, k, n,
                                    comm.world_size, np.float32,
                                    bidirectional) is not None
        return {op: {Algorithm.XLA: [1.0] * len(ms),
                     Algorithm.PALLAS: [0.5] * len(ms)} for op in ops}

    monkeypatch.setattr(autotune, "measure_collective_matmul", fake_measure)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        # pows include 2^13 = 8192 rows: the mmrs accumulator misses
        # every plan arm there and must be dropped; the agmm side now
        # resolves through the round-20 n-block arm (mb/nmb) so 8192
        # stays IN its sweep — but only while the register allows the
        # arm: with cmatmul_nblock off the old drop must come back
        tuned = autotune.autotune_collective_matmul(accl, pows=(7, 13),
                                                    reps=1)
        assert seen["agmm"] == [128, 8192] and seen["mmrs"] == [128]
        cm.set_nblock_enabled(False)
        try:
            autotune.autotune_collective_matmul(accl, pows=(7, 13), reps=1)
        finally:
            cm.set_nblock_enabled(True)
        assert seen["agmm"] == [128] and seen["mmrs"] == [128]
        assert tuned.ag_matmul_threshold == 128 * 512 * 4
        assert tuned.rs_matmul_threshold == 128 * 512 * 4
        comm = accl.global_comm()
        assert algorithms.select(operation.allgather_matmul,
                                 tuned.ag_matmul_threshold, comm,
                                 tuned) == Algorithm.PALLAS
    finally:
        accl.config = orig


def test_autotune_collective_matmul_noop_off_ici(accl):
    """SIM/DCN transports pass the config through untouched (the kernels
    would measure the simulator)."""
    tuned = autotune.autotune_collective_matmul(accl, accl.config)
    assert tuned.ag_matmul_threshold == accl.config.ag_matmul_threshold
    assert tuned.rs_matmul_threshold == accl.config.rs_matmul_threshold

def test_autotune_collective_matmul_aspect_classes(accl, monkeypatch):
    """Round 9: the default sweep measures one crossover per (k, n)
    aspect-ratio class and records it in the per-class registers (the
    square class also lands in the scalar select() reads). The sweep
    filter admits STREAMING plans — shapes that fell out of the round-8
    sweep as 'no plan' now measure the k-blocked kernel."""
    from accl_tpu.config import TransportBackend
    from accl_tpu.ops import collective_matmul as cm

    seen = []

    def fake_measure(comm, ms, algos, k=512, n=512, dt=None, reps=1,
                     bidirectional=True, ops=("agmm", "mmrs"),
                     wire_dtype=None):
        # the tuned config carries no wire dtype -> the measured
        # programs must be pinned to full precision explicitly, never
        # inheriting the module session register (review-r9 finding)
        assert wire_dtype == "off"
        seen.append((cm.aspect_class(k, n), ops[0], tuple(ms)))
        return {op: {Algorithm.XLA: [1.0] * len(ms),
                     Algorithm.PALLAS: [0.5] * len(ms)} for op in ops}

    monkeypatch.setattr(autotune, "measure_collective_matmul", fake_measure)
    orig = accl.config
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        tuned = autotune.autotune_collective_matmul(accl, pows=(7,),
                                                    reps=1)
        classes = {c for c, _, _ in seen}
        assert classes == {"square", "wide", "tall"}
        # every class recorded; the square crossover is also the scalar
        assert set(tuned.ag_matmul_class_thresholds) == classes
        assert set(tuned.rs_matmul_class_thresholds) == classes
        assert tuned.ag_matmul_threshold \
            == tuned.ag_matmul_class_thresholds["square"] == 128 * 512 * 4
        # wide class crossover keys on ITS k (256): different register
        assert tuned.ag_matmul_class_thresholds["wide"] == 128 * 256 * 4
        # the tuned dicts write through to the kernel-module resolution
        accl.config = tuned
        assert cm._ag_threshold(256, 1024) \
            == tuned.ag_matmul_class_thresholds["wide"]
        # explicit k/n narrows the sweep to that single class
        seen.clear()
        autotune.autotune_collective_matmul(accl, pows=(7,), k=512, n=512,
                                            reps=1)
        assert {c for c, _, _ in seen} == {"square"}
    finally:
        accl.config = orig


def test_autotune_collective_matmul_sweeps_streaming_shapes(accl,
                                                            monkeypatch):
    """The plan filter admits mode=stream sizes: a row count whose
    resident plan misses the budget stays IN the sweep now (round 8
    dropped it, timing nothing)."""
    from accl_tpu.config import TransportBackend
    from accl_tpu.ops import collective_matmul as cm

    W = accl.global_comm().world_size
    # 2^13 rows at (512, 512): resident output panel alone busts the
    # budget; the k-blocked plan must not (it keeps (mh, n) f32 accs)
    plan = cm.agmm_plan(2 ** 13, 512, 512, W, np.float32, True)
    assert plan is None or plan["mode"] == "stream"


def test_autotune_zero_fsdp_gates(accl, monkeypatch):
    """The layerwise ZeRO schedule register tunes only where a
    measurement would mean something: off ICI the config passes through
    untouched, and on ICI a rung whose kernels cannot run (so the fused
    step would measure its own committed fallback) also passes through
    — zero_overlap keeps its session value either way."""
    from accl_tpu.config import TransportBackend
    from accl_tpu.ops import collective_matmul as cm

    cfg = autotune.autotune_zero_fsdp(accl)         # SIM transport
    assert cfg.zero_overlap == accl.config.zero_overlap
    orig = accl.config
    # the TPU interpreter runs the kernels on this rung: stand in for one
    # that cannot
    monkeypatch.setattr(cm, "_kernels_available", lambda: False)
    try:
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        cfg = autotune.autotune_zero_fsdp(accl)     # ICI, no kernels here
        assert cfg.zero_overlap == accl.config.zero_overlap
    finally:
        accl.config = orig


def test_autotune_sched_synth_gates(accl):
    """The schedule synthesizer's calibration stage measures only where
    it would mean something: off ICI (this rung) the config passes
    through untouched; on ICI a mesh with no declared or detected torus
    also passes through (AUTO never dispatches the multi-axis plan
    there, so there is nothing to seed)."""
    from accl_tpu.config import TransportBackend

    cfg = autotune.autotune_sched_synth(accl)       # SIM transport
    assert cfg.sched_alpha_us == accl.config.sched_alpha_us
    assert cfg.sched_synthesis == accl.config.sched_synthesis
    orig = accl.config
    try:
        # ICI but no torus shape: untouched
        accl.config = accl.config.replace(transport=TransportBackend.ICI)
        cfg = autotune.autotune_sched_synth(accl)
        assert cfg.sched_beta_gbps == accl.config.sched_beta_gbps
        # ICI WITH a declared torus: the fit runs, α/β become measured
        # values and the go/no-go resolves from a real A/B
        accl.config = accl.config.replace(
            transport=TransportBackend.ICI, sched_mesh_shape=[2, 4])
        cfg = autotune.autotune_sched_synth(accl, pows=(8, 12), reps=1)
        assert cfg.sched_alpha_us > 0 and cfg.sched_beta_gbps > 0
        assert isinstance(cfg.sched_synthesis, bool)
        # round 16: the pipelined calibration rode along — a measured
        # per-chunk startup term and a resolved go/no-go (chunks=1
        # retires the pipelined candidate where chunking never won)
        assert cfg.sched_pipeline_startup_us > 0
        assert cfg.sched_pipeline_chunks in (1, 2, 4)
    finally:
        accl.config = orig


def test_autotune_dcn_twotier_gates(accl, monkeypatch):
    """The DCN tier's calibration stage measures only where it means
    something: off DCN (this rung) the config passes through untouched;
    on DCN without a host-aligned slice boundary it also passes through
    (there is no two-tier schedule to tune); on DCN WITH a slice
    boundary the α/β fit runs and the compressed go/no-go resolves
    from a real A/B into dcn_wire_dtype."""
    from accl_tpu.config import TransportBackend

    cfg = autotune.autotune_dcn_twotier(accl)       # SIM transport
    assert cfg.sched_dcn_alpha_us == accl.config.sched_dcn_alpha_us
    assert cfg.dcn_wire_dtype == accl.config.dcn_wire_dtype
    orig = accl.config
    comm = accl.global_comm()
    try:
        # DCN but no slice boundary: untouched
        accl.config = accl.config.replace(transport=TransportBackend.DCN)
        assert comm.hosts_shape() is None
        cfg = autotune.autotune_dcn_twotier(accl)
        assert cfg.sched_dcn_beta_gbps == accl.config.sched_dcn_beta_gbps
        assert cfg.dcn_wire_dtype == accl.config.dcn_wire_dtype
        # DCN with a (monkeypatched) host-aligned boundary: the fit
        # runs, the DCN pair becomes measured values and the go/no-go
        # records a real verdict
        monkeypatch.setattr(type(comm), "hosts_shape",
                            lambda self: (2, 4))
        cfg = autotune.autotune_dcn_twotier(accl, pows=(8, 12), reps=1)
        assert cfg.sched_dcn_alpha_us > 0 and cfg.sched_dcn_beta_gbps > 0
        assert cfg.dcn_wire_dtype in ("off", "bf16")
    finally:
        accl.config = orig


def test_autotune_serving_throughput_gates(accl):
    """Round-18 serving autotunes measure only on a real TPU backend
    (the interpret rung would tune the emulator): on this rung both
    pass the config through untouched, and both are wired into
    autotune_session's stage list + the world-1 single-chip chain."""
    import inspect

    cfg = autotune.autotune_prefill(accl)
    assert cfg.flash_prefill == accl.config.flash_prefill
    cfg = autotune.autotune_spec_decode(accl)
    assert cfg.spec_decode_tokens == accl.config.spec_decode_tokens
    src = inspect.getsource(autotune.autotune_session)
    assert "autotune_prefill" in src
    assert "autotune_spec_decode" in src
