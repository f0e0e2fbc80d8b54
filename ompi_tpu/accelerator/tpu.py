"""accelerator/tpu — the jax/PJRT-backed accelerator component.

Reference peer: opal/mca/accelerator/cuda (accelerator_cuda.c) — but where
the cuda component wraps driver-API pointers, this one wraps opaque
``jax.Array`` buffers: identity is the Python type + PJRT client, copies
are device_put/asarray on PJRT streams, and bandwidth comes from a
per-generation HBM table (the reference reads it from NVML;
libtpu exposes no query, so we carry the published specs).
"""

from __future__ import annotations

import struct
from typing import Any, Optional

import numpy as np

from ompi_tpu.accelerator.base import (
    AcceleratorModule,
    accelerator_framework,
)
from ompi_tpu.core.errors import MPIError, ERR_ARG
from ompi_tpu.mca.component import Component, ComponentFatal
from ompi_tpu.mca.var import register_var, get_var

# Published peaks per chip, keyed by the device_kind JAX reports:
# (dense bf16 FLOP/s, HBM GB/s). Sources: Google Cloud TPU system
# architecture pages per generation ("TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s) and How to Scale Your Model's chip table. A TPU kind that
# is not here is an error, not a default (reference analog: get_mem_bw
# via NVML).
PEAKS = {
    "TPU v2": (45e12, 700.0),
    "TPU v3": (123e12, 900.0),
    "TPU v4": (275e12, 1228.0),
    "TPU v5 lite": (197e12, 819.0),  # v5e
    "TPU v5": (459e12, 2765.0),      # v5p
    "TPU v6 lite": (918e12, 1640.0),  # v6e
}
# the CPU backend has no published peak; a nominal host bandwidth keeps
# the CPU mesh's bandwidth-model users running
_CPU_MEM_BW_GBS = 50.0


def peaks(device) -> tuple:
    """(bf16 FLOP/s, HBM GB/s) of a TPU device; raises for a TPU kind
    missing from the table."""
    kind = device.device_kind
    if kind not in PEAKS:
        raise MPIError(ERR_ARG, f"no published peaks for device_kind "
                                f"{kind!r}: add it to accelerator/tpu.py "
                                "PEAKS with its source")
    return PEAKS[kind]


register_var("accelerator", "tpu_mem_bw", 0.0, float,
             help="Override the HBM bandwidth estimate (GB/s); 0=auto",
             level=7)


class JaxAccelerator(AcceleratorModule):
    NAME = "tpu"

    def __init__(self):
        import jax

        self._jax = jax
        self._devices = jax.devices()

    # --- identity ------------------------------------------------------
    def check_addr(self, obj: Any) -> bool:
        return isinstance(obj, self._jax.Array)

    def num_devices(self) -> int:
        return len(self._devices)

    def get_device(self, obj: Any) -> int:
        devs = list(obj.devices())
        return min(d.id for d in devs)

    def get_buffer_id(self, obj: Any) -> int:
        # jax.Array has no stable buffer address across donation; object
        # identity is the closest analog of the reference's buffer id.
        return id(obj)

    def device_can_access_peer(self, dev_a: int, dev_b: int) -> bool:
        # Every chip in a slice is ICI-connected; a single PJRT client
        # only ever sees one slice.
        n = self.num_devices()
        return 0 <= dev_a < n and 0 <= dev_b < n

    def get_mem_bw(self, device: int = 0) -> float:
        override = get_var("accelerator", "tpu_mem_bw")
        if override:
            return float(override)
        dev = self._devices[device]
        if dev.platform == "cpu":
            return _CPU_MEM_BW_GBS
        return peaks(dev)[1]

    # --- alloc / copy --------------------------------------------------
    def mem_alloc(self, nbytes: int, device: int = 0) -> Any:
        import jax.numpy as jnp

        arr = jnp.zeros(nbytes, dtype=jnp.uint8)
        return self._jax.device_put(arr, self._devices[device])

    def mem_release(self, obj: Any) -> None:
        obj.delete()

    def mem_copy_to_host(self, obj: Any) -> np.ndarray:
        return np.asarray(obj)

    def mem_copy_to_device(self, host: np.ndarray,
                           device: Optional[int] = None) -> Any:
        dev = self._devices[device] if device is not None else None
        host = np.ascontiguousarray(host)
        if self._devices[0].platform == "cpu":
            # CPU-backend device_put aliases the numpy buffer zero-copy;
            # a "copy to device" must snapshot (real HTOD DMA always does)
            host = host.copy()
        return self._jax.device_put(host, dev)

    def synchronize(self, obj: Any = None) -> None:
        if obj is not None:
            obj.block_until_ready()
            return
        # a device runs its queue in order: a trivial op behind it on
        # every device drains them all
        for dev in self._devices:
            (self._jax.device_put(0, dev) + 0).block_until_ready()

    # --- IPC -----------------------------------------------------------
    # Wire format: u8 dtype-name length | dtype name | u8 ndim |
    # i64 dims... | raw row-major bytes.
    def get_ipc_handle(self, obj: Any) -> bytes:
        host = np.ascontiguousarray(np.asarray(obj))
        name = host.dtype.name.encode()
        hdr = struct.pack("<B", len(name)) + name
        hdr += struct.pack("<B", host.ndim)
        hdr += struct.pack(f"<{host.ndim}q", *host.shape)
        return hdr + host.tobytes()

    def open_ipc_handle(self, handle: bytes) -> Any:
        mv = memoryview(handle)
        nlen = mv[0]
        name = bytes(mv[1 : 1 + nlen]).decode()
        off = 1 + nlen
        ndim = mv[off]
        off += 1
        dims = struct.unpack_from(f"<{ndim}q", mv, off)
        off += 8 * ndim
        try:
            dt = np.dtype(name)
        except TypeError:
            import ml_dtypes

            dt = np.dtype(getattr(ml_dtypes, name))
        host = np.frombuffer(mv[off:], dtype=dt).reshape(dims)
        return self.mem_copy_to_device(host)


class TpuComponent(Component):
    NAME = "tpu"
    PRIORITY = 50

    def query(self, **ctx: Any) -> Optional[AcceleratorModule]:
        # only a missing jax selects null; a device that fails to come
        # up raises rather than hide behind the host-only stub
        try:
            import jax  # noqa: F401
        except ImportError:
            return None
        try:
            return JaxAccelerator()
        except Exception as e:
            raise ComponentFatal(f"jax devices failed to come up: {e}") \
                from e


class NullAccelerator(AcceleratorModule):
    """Host-only stub (reference: opal/mca/accelerator/null) — the test
    fake: nothing is ever device memory, copies are identity."""

    NAME = "null"

    def check_addr(self, obj: Any) -> bool:
        return False

    def num_devices(self) -> int:
        return 0

    def get_device(self, obj: Any) -> int:
        raise MPIError(ERR_ARG, "null accelerator owns no buffers")

    def get_buffer_id(self, obj: Any) -> int:
        return id(obj)

    def device_can_access_peer(self, dev_a: int, dev_b: int) -> bool:
        return False

    def get_mem_bw(self, device: int = 0) -> float:
        return _CPU_MEM_BW_GBS

    def mem_alloc(self, nbytes: int, device: int = 0) -> Any:
        return np.zeros(nbytes, dtype=np.uint8)

    def mem_release(self, obj: Any) -> None:
        pass

    def mem_copy_to_host(self, obj: Any) -> np.ndarray:
        return np.asarray(obj)

    def mem_copy_to_device(self, host: np.ndarray,
                           device: Optional[int] = None) -> Any:
        return np.array(host)

    def synchronize(self, obj: Any = None) -> None:
        pass

    def get_ipc_handle(self, obj: Any) -> bytes:
        raise MPIError(ERR_ARG, "null accelerator has no IPC")

    def open_ipc_handle(self, handle: bytes) -> Any:
        raise MPIError(ERR_ARG, "null accelerator has no IPC")


class NullComponent(Component):
    NAME = "null"
    PRIORITY = 0  # last resort (reference: null's -9 priority analog)

    def query(self, **ctx: Any) -> Optional[AcceleratorModule]:
        return NullAccelerator()


accelerator_framework.register(TpuComponent())
accelerator_framework.register(NullComponent())
