"""TPU pod topology discovery -> PlatformData tags.

Reference analog: agent/src/platform (K8s/host metadata collection for
SmartEncoding tags). TPU-native: slice/host/chip/core identity from
jax.devices() plus TPU-VM environment, without requiring the metadata server
(TPU_SKIP_MDS_QUERY setups still resolve).
"""

from __future__ import annotations

import os
import socket

from deepflow_tpu.proto import pb
from deepflow_tpu.tpuprobe.sources import jax_backends_initialized


def collect_platform_data(use_jax: bool = True) -> "pb.PlatformData":
    """Best-effort topology snapshot. Never imports JAX and never
    initializes its backends: devices are listed only once the job has
    brought them up itself (before that, the snapshot has no devices and
    the caller collects again later)."""
    p = pb.PlatformData()
    p.hostname = socket.gethostname()
    try:
        p.host_ip = socket.gethostbyname(p.hostname)
    except OSError:
        p.host_ip = "127.0.0.1"
    p.pod_name = os.environ.get("HOSTNAME", "")
    p.pod_namespace = os.environ.get("POD_NAMESPACE", "")
    p.tpu_pod_name = os.environ.get(
        "TPU_NAME", os.environ.get("TPU_POD_NAME", ""))
    p.tpu_worker_id = os.environ.get("TPU_WORKER_ID", "0")
    p.accelerator_type = os.environ.get("TPU_ACCELERATOR_TYPE", "")
    p.runtime_version = os.environ.get("TPU_RUNTIME_VERSION", "")

    if use_jax and jax_backends_initialized():
        import jax
        devices = jax.devices()
        slices = set()
        for d in devices:
            info = p.devices.add()
            info.device_id = d.id
            info.chip_id = getattr(d, "id", 0)
            info.core_id = getattr(d, "core_on_chip", 0)
            slice_idx = getattr(d, "slice_index", 0) or 0
            info.slice_id = slice_idx
            slices.add(slice_idx)
            info.device_kind = getattr(d, "device_kind", "")
            coords = getattr(d, "coords", None)
            if coords:
                info.coords.extend(int(c) for c in coords)
            stats = {}
            try:
                stats = d.memory_stats() or {}
            except Exception:
                pass
            info.hbm_bytes = int(stats.get("bytes_limit", 0))
        p.slice_count = max(1, len(slices))
        if not p.accelerator_type and devices:
            p.accelerator_type = getattr(devices[0], "device_kind", "")
    return p
