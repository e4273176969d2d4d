"""The generator: deterministic per seed, every pair once per round."""
import itertools

import pytest

from benchmark import ops, spec, work
from benchmark.generators import rounds as traffic

SEEDS = [0, 7, 2**31 + 11, 2**40 + 3]


@pytest.fixture(scope="module", params=["small_msg", "grad_sync"])
def mix(request):
    return spec._read_json(f"{spec.ROOT}/benchmark/traffic/"
                           f"{request.param}.json")


@pytest.mark.parametrize("seed", SEEDS)
def test_rounds_are_deterministic_and_complete(mix, seed):
    want = traffic.pairs(mix)
    a = list(itertools.islice(traffic.rounds(mix, seed), 3))
    b = list(itertools.islice(traffic.rounds(mix, seed), 3))
    assert a == b
    for rnd in a:
        assert sorted(rnd) == sorted(want) and len(rnd) == len(want)
    assert a[0] != a[1]


def test_seeds_differ_in_order_only(mix):
    r1 = next(traffic.rounds(mix, 1))
    r2 = next(traffic.rounds(mix, 2))
    assert r1 != r2 and sorted(r1) == sorted(r2)


def test_pair_counts():
    small = spec._read_json(f"{spec.ROOT}/benchmark/traffic/small_msg.json")
    grad = spec._read_json(f"{spec.ROOT}/benchmark/traffic/grad_sync.json")
    assert len(traffic.pairs(small)) == 8 * 16
    assert len(traffic.pairs(grad)) == 3 * 7
    assert traffic.sizes(grad)[0] == 16 << 20 and \
        traffic.sizes(grad)[-1] == 1 << 30


def test_unknown_key_is_refused():
    with pytest.raises(ValueError):
        traffic.pairs({"ops": ["allreduce"], "size_unit": "bytes",
                       "size_pow2": [4, 5], "size_is": "nccl", "rate": 3})


MIB = 1 << 20
GRAD = {"size_unit": "bytes", "size_is": "nccl"}
SMALL = {"size_unit": "elements", "size_is": "count"}


@pytest.mark.parametrize("n", [1, 4])
def test_nccl_geometry_and_bytes(n):
    S = 64 * MIB
    ar = ops.geometry(ops.load("allreduce"), S, GRAD, n, 4)
    ag = ops.geometry(ops.load("allgather"), S, GRAD, n, 4)
    rs = ops.geometry(ops.load("reduce_scatter"), S, GRAD, n, 4)
    assert (ar.count, ar.in_elems, ar.out_elems) == (S // 4, S // 4, S // 4)
    assert (ag.count, ag.in_elems, ag.out_elems) == \
        (S // 4 // n, S // 4 // n, S // 4)
    assert (rs.count, rs.in_elems, rs.out_elems) == \
        (S // 4 // n, S // 4, S // 4 // n)
    for g in (ar, ag, rs):
        assert work.nccl_bytes(g, 4) == S
    f = (n - 1) / n
    assert work.ici_bytes_sent("allreduce", ar, n, 4) == 2 * f * S
    assert work.ici_bytes_sent("allgather", ag, n, 4) == f * S
    assert work.ici_bytes_sent("reduce_scatter", rs, n, 4) == f * S
    assert work.hbm_bytes(ar, 4) == 2 * S
    assert work.hbm_bytes(ag, 4) == S + S // n
    assert work.hbm_bytes(rs, 4) == S + S // n


def test_least_time_v5e():
    from benchmark import peaks

    p = peaks.peaks("TPU v5 lite")
    g = ops.geometry(ops.load("allreduce"), 1 << 30, GRAD, 4, 4)
    # 1.5 GiB sent over 200 GB/s is the bound, not 2 GiB over 819 GB/s
    assert work.least_time_s("allreduce", g, 4, 4, p) == \
        pytest.approx(1.5 * 2**30 / 200e9)
    g1 = ops.geometry(ops.load("allreduce"), 1 << 30, GRAD, 1, 4)
    assert work.least_time_s("allreduce", g1, 1, 4, p) == \
        pytest.approx(2 * 2**30 / 819e9)
    with pytest.raises(ValueError):
        peaks.peaks("TPU v99")


def test_count_geometry_world1():
    small = spec._read_json(f"{spec.ROOT}/benchmark/traffic/small_msg.json")
    for name in small["ops"]:
        g = ops.geometry(ops.load(name), 1 << 19, SMALL, 1, 4)
        assert g.count == g.in_elems == g.out_elems == 1 << 19
