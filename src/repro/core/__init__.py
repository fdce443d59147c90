"""repro.core — the paper's contribution as a composable JAX module.

Public API mirrors OpenCLIPER's class names (CLapp, Data, XData, KData,
NDArray, Process) with JAX/TPU semantics.  See the paper->JAX concept map
in README.md and the layer guide in docs/architecture.md.
"""
from . import trace
from .app import (
    CLapp,
    CLIPERApp,
    DataHandle,
    DeviceTraits,
    DeviceType,
    INVALID_HANDLE,
    NoMatchingDeviceError,
    PlatformTraits,
    compile_cache_dir,
    enable_compile_cache,
)
from .arena import (
    ALIGN,
    ArenaEntry,
    ArenaLayout,
    batched_spec,
    device_view,
    pack_device,
    pack_host,
    pack_tree_host,
    plan_layout,
    split_batched_blob,
    unpack_device,
    unpack_host,
    unpack_tree_host,
    write_host,
)
from .data import Data, KData, NDArray, XData
from .process import (
    DonatedBufferError,
    Port,
    PortError,
    Process,
    ProcessChain,
    ProfileParameters,
    PureLaunchable,
    aot_compile,
    compile_cache_stats,
)
from .graph import GraphError, Node, Pipeline
from .registry import KernelCompileError, KernelEntry, KernelRegistry, kernel
from .stream import BatchedProcess, StreamQueue, stream_launch
from .sync import Coherence, SyncSource

__all__ = [
    "ALIGN", "ArenaEntry", "ArenaLayout", "BatchedProcess", "CLapp",
    "CLIPERApp", "Coherence", "Data", "DataHandle", "DeviceTraits",
    "DeviceType", "DonatedBufferError", "GraphError", "INVALID_HANDLE",
    "KData", "KernelCompileError", "KernelEntry", "KernelRegistry",
    "NDArray", "Node", "NoMatchingDeviceError", "Pipeline", "PlatformTraits",
    "Port", "PortError", "Process", "ProcessChain", "ProfileParameters",
    "PureLaunchable", "StreamQueue", "SyncSource", "XData",
    "aot_compile",
    "batched_spec", "compile_cache_dir", "compile_cache_stats",
    "device_view", "enable_compile_cache", "kernel",
    "pack_device", "pack_host", "pack_tree_host", "plan_layout",
    "split_batched_blob", "stream_launch", "trace",
    "unpack_device", "unpack_host", "unpack_tree_host", "write_host",
]
