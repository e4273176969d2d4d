"""ACCL-TPU: a TPU-native collective communication framework.

A from-scratch re-expression of the capabilities of Xilinx/ACCL (an MPI-like
collective offload library for network-attached FPGAs) for TPUs: collectives
are compiled XLA programs over device meshes, buffers are shards of global
``jax.Array``s, arithmetic/compression plugins are Pallas kernels, and the
eager/rendezvous two-sided protocol becomes a tag-matched send/recv engine on
top of single-pair ``ppermute`` moves. See SURVEY.md for the design map.
"""

# Under the per-rank launcher (accl_tpu.launch — the mpirun analog), join
# the multi-controller runtime before any JAX backend use.
from . import multiproc as _multiproc

_multiproc.ensure_initialized()

from . import obs
from .accl import ACCL
from .arithconfig import ArithConfig, DEFAULT_ARITH_CONFIG
from .buffer import BaseBuffer, Buffer, BufferSlice, DummyBuffer
from .communicator import Communicator, Rank
from .config import ACCLConfig, Algorithm, TransportBackend
from . import fault
from .constants import (
    ACCLCommInvalidatedError,
    ACCLError,
    ACCLPeerFailedError,
    ACCLTimeoutError,
    TAG_ANY,
    cfgFunc,
    compressionFlags,
    dataType,
    errorCode,
    operation,
    reduceFunction,
)
from .request import Request, RequestQueue, requestStatus
from .utils import Timer

__version__ = "0.3.0"

__all__ = [
    "ACCL",
    "ACCLCommInvalidatedError",
    "ACCLConfig",
    "ACCLError",
    "ACCLPeerFailedError",
    "ACCLTimeoutError",
    "Algorithm",
    "ArithConfig",
    "BaseBuffer",
    "Buffer",
    "BufferSlice",
    "Communicator",
    "DEFAULT_ARITH_CONFIG",
    "DummyBuffer",
    "Rank",
    "Request",
    "RequestQueue",
    "TAG_ANY",
    "Timer",
    "TransportBackend",
    "cfgFunc",
    "compressionFlags",
    "dataType",
    "errorCode",
    "fault",
    "obs",
    "operation",
    "reduceFunction",
    "requestStatus",
]
