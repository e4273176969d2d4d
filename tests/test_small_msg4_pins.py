"""What AUTO resolves for the benchmark cell ``small_msg.4chip``: four
ranks on a v5e 2x2 host over ICI (declared here, detected from device
coordinates on the chip), fp32, at one size in each branch the cell
crosses: the latency tier (2**4 elements), the first size above it
(2**11: 8 KiB), the first rendezvous size (2**14: 64 KiB) and the
ladder's Pallas threshold (2**18: 1 MiB).

Each collective pins the algorithm and the plan source that
``algorithms.select_plan`` resolves with the session's byte convention
(``count`` elements, reduce_scatter its whole input); send/recv pins
the protocol the session takes. A change to a threshold or to the cost
model that moves this cell shows here first."""
import jax
import pytest

import accl_tpu
from accl_tpu import Algorithm, dataType
from accl_tpu.config import ACCLConfig, TransportBackend
from accl_tpu.constants import operation
from accl_tpu.obs import metrics
from accl_tpu.parallel import algorithms

WORLD = 4
X, FLAT = Algorithm.XLA, Algorithm.FLAT
TIER, COST = "latency_tier", "cost_model"

#: (log2 count, op) -> (algorithm, plan source); None: the op is not
#: synthesized, so the ladder alone decides and there is no plan
PINS = {
    (4, "bcast"): (X, None), (4, "scatter"): (X, None),
    (4, "reduce"): (X, None), (4, "gather"): (X, None),
    (4, "allreduce"): (FLAT, TIER), (4, "reduce_scatter"): (X, TIER),
    (4, "allgather"): (X, TIER),
    (11, "bcast"): (X, None), (11, "scatter"): (X, None),
    (11, "reduce"): (X, None), (11, "gather"): (X, None),
    (11, "allreduce"): (X, COST), (11, "reduce_scatter"): (X, COST),
    (11, "allgather"): (X, COST),
    (14, "bcast"): (FLAT, None), (14, "scatter"): (FLAT, None),
    (14, "reduce"): (FLAT, None), (14, "gather"): (FLAT, None),
    (14, "allreduce"): (X, COST), (14, "reduce_scatter"): (X, COST),
    (14, "allgather"): (X, COST),
    (18, "bcast"): (FLAT, None), (18, "scatter"): (FLAT, None),
    (18, "reduce"): (FLAT, None), (18, "gather"): (FLAT, None),
    (18, "allreduce"): (X, COST), (18, "reduce_scatter"): (X, COST),
    (18, "allgather"): (X, COST),
}

#: log2 count -> the send protocol, "eager_fast" where the latency-tier
#: fast path takes it
PROTOCOL = {4: "eager_fast", 11: "eager", 14: "rendezvous",
            18: "rendezvous"}


@pytest.fixture(scope="module")
def acc():
    cfg = ACCLConfig(transport=TransportBackend.ICI,
                     sched_mesh_shape=[2, 2])
    inst = accl_tpu.ACCL(devices=jax.devices()[:WORLD], config=cfg)
    yield inst
    inst.deinit()


@pytest.mark.parametrize("p,op", sorted(PINS), ids=lambda v: str(v))
def test_collective_resolution(acc, p, op):
    count = 1 << p
    nbytes, cnt = count * 4, count
    if op == "reduce_scatter":
        nbytes, cnt = nbytes * WORLD, cnt * WORLD
    algo, plan = algorithms.select_plan(getattr(operation, op), nbytes,
                                        acc.global_comm(), acc.config,
                                        count=cnt)
    source = None if plan is None else plan.source
    assert (algo, source) == PINS[(p, op)]


@pytest.mark.parametrize("p", sorted(PROTOCOL))
def test_sendrecv_protocol(acc, p):
    count = 1 << p
    s = acc.create_buffer(count, dataType.float32)
    r = acc.create_buffer(count, dataType.float32)
    before = metrics.snapshot()
    acc.send(s, count, src=0, dst=1, tag=7)
    acc.recv(r, count, src=0, dst=1, tag=7)
    d = metrics.delta(before)
    protocols = [k for k, v in d["counters"].items()
                 if k.startswith("accl_sendrecv_protocol_total") and v]
    assert len(protocols) == 1
    fast = d["histograms"].get(
        'accl_latency_dispatch_seconds{path="eager_send"}', {}).get("count")
    got = protocols[0].split('"')[1]
    if fast:
        got += "_fast"
    assert got == PROTOCOL[p]
