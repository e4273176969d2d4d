"""``correct`` comes out true for the library and false for the control
and for each fault the cell can have, driving the rest of a run on the
CPU at a size a test can hold (the harness's look for a chip skipped).

Faults, planted under the timed path: a call that leaves its output
unchanged; half of the batch left out (the call made with half the
count); the exchange between chips left out (each rank's output from
its own input alone); one answer altered where it is produced. The
control is the reference in the library's place in bfloat16."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import cellrun, control, ops, reference, spec

SIZES = {"small_msg.1chip": [4, 6], "grad_sync.4chip": [12, 13]}


def _cell(name):
    cell = spec.resolve_cell(name)
    cell.traffic["size_pow2"] = SIZES[name]
    return cell


def unchanged(acc, op, src, dst, count, config):
    pass


def half(acc, op, src, dst, count, config):
    ops.call(acc, op, src, dst, max(count // 2, 1), config)


def altered(acc, op, src, dst, count, config):
    ops.call(acc, op, src, dst, count, config)
    r = list(op.rows(acc.world_size, config))[-1]
    dst.device_store(dst.device_view().at[r, 0].add(1.0))


def no_exchange(acc, op, src, dst, count, config):
    """Each rank's output from its own input row alone."""
    n = acc.world_size
    x, prev = src.device_view(), dst.device_view()
    rows, prev_rows = reference.row_shards(x), reference.row_shards(prev)
    defined = set(op.rows(n, config))
    fn = reference.row_fn(op, count, config, jnp.float32)
    out = []
    for r in range(n):
        if r not in defined:
            out.append(prev_rows[r])
            continue
        xs = [rows[r] if k == r else jnp.zeros_like(rows[r])
              for k in range(n)]
        out.append(fn(jnp.int32(r), *xs))
    dst.device_store(jax.make_array_from_single_device_arrays(
        prev.shape, prev.sharding, out))


FAULTS = {"unchanged": unchanged, "half": half, "altered": altered,
          "no_exchange": no_exchange,
          "bf16_control": control.lower_precision(jnp.bfloat16)}


def _run(name, call, seed=2**40 + 9):
    cell = _cell(name)
    devices = jax.devices()[:cell.chips]
    return cellrun.run_cell(cell, seed, 0.2, False, devices,
                            time.perf_counter(), call=call)


@pytest.mark.parametrize("name", sorted(SIZES))
def test_library_is_correct(name):
    res = _run(name, ops.call)
    assert res["correct"], res["checks"]
    assert res["checks"]["max_abs_gap"]["value"] == 0.0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m.name for m in
                                   spec.resolve_cell(name).end_to_end}
    assert res["window"]["compiles"] == 0


@pytest.mark.parametrize("name,fault", [
    (n, f) for n in sorted(SIZES) for f in sorted(FAULTS)
    if not (f == "no_exchange" and spec.resolve_cell(n).chips == 1)])
def test_fault_is_not_correct(name, fault):
    res = _run(name, FAULTS[fault])
    assert not res["correct"], (fault, res["checks"])


def test_readings_tool_separates():
    """The control's readings lie above the library's, on two seeds
    each, in one process (as ``control.py`` reads them on the chip)."""
    cell = _cell("grad_sync.4chip")
    lines = []
    lo, up = control.readings(cell, jax.devices()[:4], [3, 2**33],
                              [5, 2**35], 0.1, emit=lines.append)
    assert lo == 0.0 and up > 0.0 and len(lines) == 4
