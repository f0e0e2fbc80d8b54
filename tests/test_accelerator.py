"""Accelerator framework: device buffers through the host data path.

Reference: opal/mca/accelerator (module table accelerator.h:671-712),
the coll/accelerator staging wrapper, and pml_ob1_accelerator.c device-
buffer handling — exercised here with jax.Arrays on the virtual CPU
backend (the accelerator/null + fake-device CI pattern, SURVEY §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ompi_tpu
from ompi_tpu import COMM_WORLD
from ompi_tpu.accelerator import (
    DeviceBuffer,
    accelerator_framework,
    get_module,
    is_device_buffer,
    stage_to_host,
)
from ompi_tpu.core import op as mpi_op
from ompi_tpu.core.errors import MPIError


@pytest.fixture(scope="module")
def mod():
    return get_module()


def test_selection_prefers_tpu_component(mod):
    # With jax importable, the tpu component (priority 50) must win over
    # null (priority 0) — reference: accelerator_base_select.c.
    assert mod.NAME == "tpu"


def test_check_addr(mod):
    assert mod.check_addr(jnp.arange(4))
    assert not mod.check_addr(np.arange(4))
    assert not mod.check_addr(b"bytes")
    assert is_device_buffer(jnp.arange(4))
    assert not is_device_buffer(np.arange(4))


def test_device_queries(mod):
    assert mod.num_devices() >= 1
    arr = jnp.ones(3)
    dev = mod.get_device(arr)
    assert 0 <= dev < mod.num_devices()
    assert mod.get_mem_bw(dev) > 0
    assert mod.device_can_access_peer(0, 0)
    assert mod.get_buffer_id(arr) != mod.get_buffer_id(jnp.ones(3))


def test_alloc_copy_roundtrip(mod):
    buf = mod.mem_alloc(64)
    assert mod.check_addr(buf)
    host = np.arange(10, dtype=np.float32)
    dev = mod.mem_copy_to_device(host)
    assert mod.check_addr(dev)
    back = mod.mem_copy_to_host(dev)
    np.testing.assert_array_equal(back, host)
    mod.synchronize(dev)
    mod.mem_release(buf)


def test_ipc_handle_roundtrip(mod):
    arr = jnp.asarray(np.random.default_rng(0).normal(size=(3, 5)),
                      dtype=jnp.bfloat16)
    handle = mod.get_ipc_handle(arr)
    assert isinstance(handle, bytes)
    back = mod.open_ipc_handle(handle)
    assert mod.check_addr(back)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(arr))


def test_stage_to_host_is_readonly():
    host = stage_to_host(jnp.arange(4))
    with pytest.raises(ValueError):
        host[0] = 1


def test_send_device_array_recv_devicebuffer():
    """pt2pt with a jax send buffer and a DeviceBuffer recv — the staging
    path of pml_ob1_accelerator.c, singleton mode."""
    send = jnp.asarray(np.arange(6, dtype=np.float32) * 2)
    out = DeviceBuffer((6,), jnp.float32)
    req = COMM_WORLD.Irecv(out, source=0, tag=3)
    COMM_WORLD.Send(send, dest=0, tag=3)
    req.Wait()
    result = out.array
    assert is_device_buffer(result)
    np.testing.assert_array_equal(np.asarray(result), np.asarray(send))


def test_recv_into_raw_device_array_fails_loudly():
    # Device arrays are immutable; recv must not silently drop data.
    send = np.ones(2, np.float32)
    recv = jnp.zeros(2)
    req = COMM_WORLD.Irecv(recv, source=0, tag=4)
    with pytest.raises((MPIError, ValueError)):
        # self-BTL delivers synchronously, so the write into the
        # read-only staging copy surfaces at Send or at Wait
        COMM_WORLD.Send(send, dest=0, tag=4)
        req.Wait()


def test_allreduce_device_buffers():
    send = jnp.asarray([1.0, 2.0, 3.0], dtype=jnp.float32)
    out = DeviceBuffer((3,), jnp.float32)
    COMM_WORLD.Allreduce(send, out, op=mpi_op.SUM)
    np.testing.assert_array_equal(np.asarray(out.array),
                                  np.asarray(send))


def test_devicebuffer_tracks_updates():
    out = DeviceBuffer((2,), jnp.int32)
    first = out.array
    COMM_WORLD.Send(np.array([7, 8], np.int32), dest=0, tag=9)
    COMM_WORLD.Recv(out, source=0, tag=9)
    np.testing.assert_array_equal(np.asarray(out.array), [7, 8])
    # cache invalidated by the verb; old array object unchanged
    np.testing.assert_array_equal(np.asarray(first), [0, 0])


def test_devicebuffer_wraps_existing_array():
    init = jnp.asarray([5, 6], dtype=jnp.int32)
    db = DeviceBuffer(init)
    np.testing.assert_array_equal(db.host, [5, 6])


def test_null_component_forced():
    from ompi_tpu.accelerator import base as accel_base
    from ompi_tpu.mca.var import set_var

    set_var("accelerator", "accelerator", "null")
    accel_base._reset_selection()
    try:
        mod = get_module()
        assert mod.NAME == "null"
        assert not mod.check_addr(jnp.arange(2))
        assert mod.num_devices() == 0
    finally:
        set_var("accelerator", "accelerator", "")
        accel_base._reset_selection()


class _Dev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind = kind
        self.platform = platform


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v9 experimental"])
def test_peaks_published_or_error(kind):
    """A TPU kind with a published peak gets it; any other is an error,
    never a default."""
    from ompi_tpu.accelerator.tpu import PEAKS, peaks

    if kind in PEAKS:
        assert peaks(_Dev(kind)) == (197e12, 819.0)
    else:
        with pytest.raises(MPIError, match="no published peaks"):
            peaks(_Dev(kind))


def test_device_bringup_failure_is_fatal(monkeypatch):
    """jax present but its devices failing must raise, not fall back
    to the host-only null component."""
    from ompi_tpu.accelerator import tpu as accel_tpu
    from ompi_tpu.mca.component import ComponentFatal

    def broken():
        raise RuntimeError("TPU initialization failed")

    monkeypatch.setattr(accel_tpu, "JaxAccelerator", broken)
    with pytest.raises(ComponentFatal, match="TPU initialization failed"):
        accelerator_framework.select_all()


def test_synchronize_all_devices(mod):
    mod.synchronize()  # drains every device's queue, not just the first
    mod.synchronize(jnp.ones(2))


def test_accelerator_procmode():
    """Device buffers between real ranks (VERDICT r1 item 4 done-criterion:
    a process-mode send/allreduce of a jax array end-to-end)."""
    from tests.test_process_mode import run_mpi

    r = run_mpi(2, "tests/procmode/check_accelerator.py")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("ACCEL-OK") == 2
