"""Bring-up helpers — the ``accl_network_utils`` analog.

The reference hides network-stack bring-up behind
``accl_network_utils::initialize_accl`` (rank-vector generation from JSON
or synthetic subnets, VNx/TCP programming, port/connection opening,
then ACCL construction —
``driver/utils/accl_network_utils/include/accl_network_utils.hpp:33-75``).
On TPU the "network stack" is the device mesh, so bring-up means: pick a
backend (real TPU chips over ICI, or a virtual CPU mesh — the emulator
rung), shape it, and construct :class:`accl_tpu.ACCL` over it.
"""
from __future__ import annotations

import math
from typing import List, Optional, Sequence, Tuple

import jax

from ..communicator import Rank
from ..config import ACCLConfig, TransportBackend
from ..constants import DEFAULT_SEGMENT_SIZE


def detect_backend(devices: Optional[Sequence[jax.Device]] = None
                   ) -> TransportBackend:
    """Classify the transport the way the HWID capability word reports the
    stack type (``accl.cpp:1066-1080``): TPU devices ride ICI; multi-host
    meshes add DCN; CPU devices are the simulator."""
    devices = list(devices) if devices is not None else jax.devices()
    if not devices or devices[0].platform != "tpu":
        return TransportBackend.SIM
    hosts = {getattr(d, "process_index", 0) for d in devices}
    return TransportBackend.DCN if len(hosts) > 1 else TransportBackend.ICI


def snake_order(devices: Sequence[jax.Device]) -> List[jax.Device]:
    """Order devices so consecutive ranks are physical ICI neighbors.

    Ring algorithms hop rank r -> r+1 every step; with jax.devices()'s
    default ordering those hops can land on arbitrary chips, crossing
    multiple ICI links. A snake raster over the chip coordinates (x
    fastest, direction alternating with y, y direction alternating with z)
    makes every consecutive pair adjacent on the torus, so each ring hop
    rides exactly one link. Devices without coords (CPU emulator) are
    returned unchanged — rank order there is synthetic anyway.
    """
    devs = list(devices)
    if not devs or getattr(devs[0], "coords", None) is None:
        return devs

    def key(d):
        x, y, z = (tuple(d.coords) + (0, 0, 0))[:3]
        ys = y if z % 2 == 0 else -y
        xs = x if (z + y) % 2 == 0 else -x
        return (z, ys, xs, getattr(d, "core_on_chip", 0))

    return sorted(devs, key=key)


def generate_ranks(
    devices: Optional[Sequence[jax.Device]] = None,
    max_segment_size: int = DEFAULT_SEGMENT_SIZE,
) -> List[Rank]:
    """Synthesize the rank table (``accl_network_utils::generate_ranks``):
    one rank per device, session = device position."""
    devices = list(devices) if devices is not None else jax.devices()
    return [
        Rank(index=i, device=d, max_segment_size=max_segment_size, session=i)
        for i, d in enumerate(devices)
    ]


def mesh_shape_2d(world: int) -> Optional[Tuple[int, int]]:
    """Most-square (rows, cols) factorization for hierarchical collectives,
    or None for primes/1 (BASELINE.json '2D ICI mesh' config)."""
    if world < 4:
        return None
    for r in range(int(math.isqrt(world)), 1, -1):
        if world % r == 0:
            return (r, world // r)
    return None


def simulated_devices(n: int) -> List[jax.Device]:
    """Force an ``n``-device virtual CPU mesh — the emulator rung of the
    test ladder (SURVEY.md §4). Must run before any other JAX use in the
    process; switching an initialized process tears down live arrays."""
    if len(jax.devices()) >= n and jax.devices()[0].platform == "cpu":
        return jax.devices()[:n]
    from jax.extend import backend as _jax_backend

    jax.clear_caches()
    _jax_backend.clear_backends()
    jax.config.update("jax_num_cpu_devices", n)
    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"CPU mesh bring-up failed: {len(devices)} < {n}")
    return devices[:n]


def initialize_accl(
    devices: Optional[Sequence[jax.Device]] = None,
    simulator_ranks: Optional[int] = None,
    config: Optional[ACCLConfig] = None,
):
    """One-call bring-up (``accl_network_utils::initialize_accl``).

    ``simulator_ranks`` forces the CPU emulator rung with that many virtual
    devices (the reference's ``-f`` hardware flag, inverted); otherwise all
    visible devices are used. The returned ACCL's config records the
    detected transport backend.
    """
    from ..accl import ACCL

    if simulator_ranks is not None:
        devices = simulated_devices(simulator_ranks)
    auto = devices is None
    devices = list(devices) if devices is not None else jax.devices()
    backend = detect_backend(devices)
    cfg = (config or ACCLConfig()).replace(transport=backend)
    if auto and cfg.topology_order:
        # auto-discovered devices get the same snake ordering bare ACCL()
        # applies; an explicit caller list is never reordered
        devices = snake_order(devices)
    return ACCL(devices=devices, config=cfg)
