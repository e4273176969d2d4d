"""Test configuration: CPU-simulated 8-device mesh.

This is the "emulator" rung of the reference's test ladder (SURVEY.md §4):
ACCL runs its real firmware natively against a ZMQ fabric; we run the real
framework against XLA's CPU backend with 9 virtual devices (an 8-rank mesh
plus one spare — see the comment below). The same suite runs unchanged on
real TPU meshes.
"""
import os

# Must be set before the first JAX backend initialization.
#
# 9 devices, not 8: the suite runs 8-rank meshes, and the Pallas TPU
# interpreter can wedge when a kernel with cross-device semaphore waits
# occupies EVERY host device (observed with the segmented ring kernels at
# world=8 on an 8-device host; the same kernels complete on any larger
# host). One spare device sidesteps the interpreter scheduling artifact.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=9"
    ).strip()

import jax  # noqa: E402

# Tests run on XLA's CPU backend. Setting ACCL_TPU_HW=1 keeps the real
# TPU backend instead — the hardware rung of the test ladder
# (tests/test_tpu_hardware.py; everything else still runs wherever it
# can).
if not os.environ.get("ACCL_TPU_HW"):
    jax.config.update("jax_platforms", "cpu")
    # float64/int64 collectives are part of the ported matrix (the
    # reference's arith plugin covers f64/i64); on CPU we test them at
    # full width.
    jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import accl_tpu  # noqa: E402


@pytest.fixture(scope="session")
def world_size() -> int:
    return 8


@pytest.fixture(scope="session")
def accl() -> accl_tpu.ACCL:
    """Session-wide ACCL instance over the 8-device CPU mesh (TestEnvironment
    fixture analog, test/host/xrt/include/fixture.hpp:48-104)."""
    inst = accl_tpu.ACCL(devices=jax.devices()[:8])
    yield inst
    inst.deinit()


@pytest.fixture()
def rng() -> np.random.Generator:
    return np.random.default_rng(42)


# ---------------------------------------------------------------------------
# shared AOT lowering gate (test_chunked_schedule + test_flash_schedule):
# one copy of the Mosaic-kernel detection and buffer-plan check, so a jax
# upgrade that changes the custom-call target string is fixed in one place
# ---------------------------------------------------------------------------

import re  # noqa: E402

MOSAIC_CALL = re.compile(r'custom_call_target="tpu_custom_call"')
AOT_HBM_BYTES = 16 << 30   # v5e: 16 GiB HBM per chip


def aot_topology_devices(topology_name: str = "v5e:2x4"):
    """Devices of a described (not attached) TPU topology, or
    pytest.skip. Call it from a test or fixture only — never at import,
    in a ``skipif`` or in ``parametrize``: describing a topology loads
    libtpu, which one process at a time may hold (the
    ``on-chip-measurement`` guide, section 2)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=topology_name)
    except Exception as e:  # no libtpu here, or not THIS topology
        pytest.skip(f"TPU AOT topology {topology_name} unavailable: {e}")
    return list(topo.devices)


def assert_aot_lowered(compiled, min_kernels: int = 1) -> str:
    """The compiled module must contain the Mosaic kernels (not an
    interpret-mode callback) and its buffer plan must fit the chip.
    Returns the module text for further structural assertions."""
    txt = compiled.as_text()
    kernels = len(MOSAIC_CALL.findall(txt))
    assert kernels >= min_kernels, \
        f"expected >= {min_kernels} Mosaic kernels, found {kernels}"
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total < AOT_HBM_BYTES, f"buffer plan {total} exceeds HBM"
    return txt
