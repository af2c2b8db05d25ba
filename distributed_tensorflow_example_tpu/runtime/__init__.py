"""Runtime layer: device discovery, distributed bring-up, Server parity.

Replaces the reference's L2/L1 C++ distributed runtime (GrpcServer, Master,
Worker, Rendezvous — SURVEY.md §2.4) with the TPU-native stack: XLA:TPU +
libtpu is the native execution layer, the TSL coordination service behind
``jax.distributed`` is the control plane, and ICI/DCN collectives replace
gRPC RecvTensor push/pull.
"""

from .device import (
    available_devices,
    chip_peak_flops,
    cpu_devices,
    default_device_kind,
    enable_compilation_cache,
    local_device_count,
)
from .distributed import DistributedContext, initialize
from .server import Server

__all__ = [
    "available_devices",
    "chip_peak_flops",
    "cpu_devices",
    "default_device_kind",
    "enable_compilation_cache",
    "local_device_count",
    "DistributedContext",
    "initialize",
    "Server",
]
