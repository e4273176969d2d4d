"""BENCHMARK.json and the files it names: every cell resolves by name,
and a cell defined only by added files loads."""
import json
import os
import re
import shutil

import pytest

from benchmark import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert bench["paths"] == ["benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_resolves(bench):
    cells = spec.all_cells()
    assert set(cells) == {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name, cell in cells.items():
        reported = {m.name for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, name
        for m in cell.per_layer:
            assert m.moves in reported, (name, m.name, m.moves)
        for m in cell.end_to_end + cell.per_layer:
            assert callable(m.reader)
        assert cell.config["chips"] == cell.chips
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(cells)


def test_configs_list_their_cuts(bench):
    for c in bench["configs"]:
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert c["file"].startswith("benchmark/")
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        assert cfg["name"] == c["name"]


ALLTOALL = '''# alltoall: out[r][k*c:(k+1)*c] = x[k][r*c:(r+1)*c]
import jax
import jax.numpy as jnp

from benchmark import ops

NCCL_CHUNKED = True


def in_elems(count, n):
    return count * n


def out_elems(count, n):
    return count * n


def call(acc, src, dst, count, config, options):
    acc.alltoall(src, dst, count, **ops.DEV, **options)


def want(xs, r, count, config, rnd):
    return jnp.concatenate([jax.lax.dynamic_slice_in_dim(x, r * count, count,
                                                         axis=1)
                            for x in xs], axis=1)
'''

ARRIVALS = '''# open loop: the mix's pairs at a fixed rate, in an order from the seed
import numpy as np


def pairs(mix):
    return [(op, 1 << p) for op in mix["ops"]
            for p in range(mix["size_pow2"][0], mix["size_pow2"][1] + 1)]


def rounds(mix, seed):
    ps = pairs(mix)
    rng = np.random.default_rng(seed)
    gap = 1.0 / mix["rate_per_s"]
    t = 0.0
    while True:
        rnd = []
        for i in rng.permutation(len(ps)):
            rnd.append(ps[i] + (t,))
            t += gap
        yield rnd
'''


def _added_cell(tmp_path):
    """A copy of the benchmark plus a cell made only of added files: an
    op that no op file defines (alltoall), an open-loop generator, a mix,
    a configuration, a metric and the entries naming them. Returns the
    copy's root and what it held before the additions."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    (root / "benchmark/ops/alltoall.py").write_text(ALLTOALL)
    (root / "benchmark/generators/arrivals.py").write_text(ARRIVALS)
    (root / "benchmark/traffic/a2a_open.json").write_text(json.dumps({
        "generator": "arrivals", "ops": ["alltoall"], "size_unit": "bytes",
        "size_pow2": [12, 13], "size_is": "nccl", "rate_per_s": 50}))
    cfg = json.loads((root / "benchmark/configs/ring_v5e4.json").read_text())
    cfg["name"] = "ring_v5e4_copy"
    (root / "benchmark/configs/ring_v5e4_copy.json").write_text(
        json.dumps(cfg))
    (root / "benchmark/metrics/calls_per_s.py").write_text(
        "def read(run):\n    return len(run['calls']) / run['window_s']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ring_v5e4_copy", "source": "x",
                             "file": "benchmark/configs/ring_v5e4_copy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "a2a_open.4chip",
                               "config": "ring_v5e4_copy",
                               "traffic": "a2a_open", "chips": 4,
                               "why": "test"})
    bench["end_to_end"].append({"name": "calls_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["a2a_open.4chip"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root, before


def test_cell_from_added_files_runs(tmp_path):
    """A new op, generator, mix, configuration and metric are files plus
    entries: nothing that is there is edited, and the cell runs and is
    checked (on four CPU devices)."""
    import time

    import jax

    from benchmark import cellrun

    root, before = _added_cell(tmp_path)
    cell = spec.resolve_cell("a2a_open.4chip", str(root))
    assert cell.traffic["ops"] == ["alltoall"]
    assert [m.name for m in cell.end_to_end] == ["setup_s", "calls_per_s"]
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p

    res = cellrun.run_cell(cell, 2**33 + 5, 0.2, False, jax.devices()[:4],
                           time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["checks"]["pairs_checked"]["value"] == 2
    # open loop at 50 calls/s: the window's 10+ calls take 0.2 s or more
    calls = res["window"]["calls"]
    assert calls >= 10 and res["window"]["seconds"] >= (calls - 1) / 50
    assert res["metrics"]["calls_per_s"]["value"] > 0


def test_config_options_reach_the_call():
    from accl_tpu import dataType

    from benchmark import ops

    assert ops.options({"compress_dtype": "bfloat16"}) == \
        {"compress_dtype": dataType.bfloat16}
    assert ops.options({}) == {}


def test_one_reader_serves_a_metric_family():
    for name in ("idle_share.small", "idle_share.large"):
        read = spec.load_reader(spec.ROOT, name)
        assert read.__code__.co_filename.endswith("metrics/idle_share.py")
    with pytest.raises(spec.SpecError):
        spec.load_reader(spec.ROOT, "no_such_metric.small")


def test_unknown_cell_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.resolve_cell("no_such.cell")
