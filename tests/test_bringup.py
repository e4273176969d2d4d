"""Bring-up helper tests (accl_network_utils analog, SURVEY.md §2.1)."""
import jax
import pytest

from accl_tpu import TransportBackend
from accl_tpu.utils import bringup


def test_generate_ranks_one_per_device():
    ranks = bringup.generate_ranks(jax.devices()[:4])
    assert [r.index for r in ranks] == [0, 1, 2, 3]
    assert [r.session for r in ranks] == [0, 1, 2, 3]
    assert all(r.device is d for r, d in zip(ranks, jax.devices()))


def test_detect_backend_cpu_is_sim():
    assert bringup.detect_backend(jax.devices()) == TransportBackend.SIM


def test_mesh_shape_2d():
    assert bringup.mesh_shape_2d(8) == (2, 4)
    assert bringup.mesh_shape_2d(16) == (4, 4)
    assert bringup.mesh_shape_2d(12) == (3, 4)
    assert bringup.mesh_shape_2d(7) is None   # prime
    assert bringup.mesh_shape_2d(2) is None   # too small for a 2D mesh


def test_initialize_accl_over_devices():
    acc = bringup.initialize_accl(devices=jax.devices()[:4])
    try:
        assert acc.world_size == 4
        hwid = acc.parse_hwid()
        assert hwid["transport"] == "sim"
        assert hwid["world_size"] == 4
    finally:
        acc.deinit()


def test_initialize_accl_simulator_ranks_reuses_cpu_mesh():
    # already on a >=4-device CPU mesh: simulated_devices must not tear down
    acc = bringup.initialize_accl(simulator_ranks=4)
    try:
        assert acc.world_size == 4
        assert acc.parse_hwid()["platform"] == "cpu"
    finally:
        acc.deinit()


def test_dryrun_multichip_fails_without_the_devices():
    """The multi-chip dry run never moves itself onto a CPU mesh: with
    fewer devices than asked it fails and names the CPU entry."""
    import __graft_entry__ as graft

    with pytest.raises(RuntimeError, match="__graft_entry__.py 64"):
        graft.dryrun_multichip(64)


@pytest.mark.parametrize("env_dir", [None, "/some/cache"])
def test_compile_cache_location(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's own to read and no
    other directory is set; otherwise <checkout>/.jax_cache."""
    from accl_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    got = compile_cache.enable(str(tmp_path))
    if env_dir is None:
        assert got == str(tmp_path / ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == got
    else:
        assert got == env_dir
        assert "jax_compilation_cache_dir" not in updates
