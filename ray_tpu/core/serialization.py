"""Serialization: pickle protocol 5 with out-of-band buffers.

Analog of python/ray/_private/serialization.py in the reference (pickle5 +
zero-copy buffer support + custom reducers). We rely on stock pickle (3.12)
plus cloudpickle for closures/lambdas in function descriptors. ObjectRefs
embedded in values are collected during serialization so the borrower
protocol can register them with their owners.
"""

from __future__ import annotations

import io
import pickle
import sys
from typing import Any, List, Tuple

import numpy as np

try:
    import cloudpickle
except ImportError:  # pragma: no cover
    from ray_tpu.utils import _cloudpickle_stub as cloudpickle  # type: ignore


class SerializedValue:
    """A value serialized into frames: frame 0 is the pickle stream, frames
    1..n are out-of-band buffers (e.g. numpy array payloads).

    Frames may be memoryviews (frame 0 is the BytesIO's exported buffer,
    out-of-band frames are ``PickleBuffer.raw()`` views of the source
    object's memory) — nothing is flattened to bytes at serialize time,
    so a consumer that writes frames straight into a mapped destination
    (``ShmObjectStore.put_serialized``) moves each byte exactly once.
    Consumers that embed frames in a pickled message must materialize
    them (``bytes(f)``) first."""

    __slots__ = ("frames", "contained_refs")

    def __init__(self, frames: List[bytes], contained_refs: List[Any]):
        self.frames = frames
        self.contained_refs = contained_refs

    @property
    def total_bytes(self) -> int:
        return sum(len(f) for f in self.frames)


_ref_cls = None  # lazy: object_ref imports back into core modules


# ------------------------------------------- device-array fast path (r13)
#
# The plasma-analog zero-copy path for accelerator arrays: a jax.Array
# pickles IN-BAND by default (its __reduce__ materializes the host copy
# into the pickle stream — a full extra traversal of the payload before
# the arena copy even starts, measured 0.45 GB/s for the dumps alone at
# 64 MiB). The typed reducer below instead emits dtype/shape metadata in
# frame 0 and the payload as an out-of-band PickleBuffer VIEW of the
# array's host buffer (np.asarray of a committed CPU array aliases the
# XLA buffer; on TPU it is the one unavoidable device->host transfer),
# so put_serialized moves each byte exactly once, source to arena.

# non-contiguous ndarrays below this stay on the stock (in-band) path:
# the contiguity normalization is a copy, only worth skipping the
# in-band stream copy for payloads that dominate serialize time
_NDARRAY_OOB_MIN_BYTES = 1 << 20


_driver_platform_settled = False


def _settle_driver_platform(jax_mod):
    """One process per chip. A worker's platform was decided when it was
    started (the CPU, unless its class leases chips), but a driver leases
    nothing: rebuilding a device array it reads must not make it start the
    TPU backend and take a chip from the workers. So a driver whose JAX has
    no backend yet is held to the CPU here, once. A driver that already
    computes with JAX (its backends are up) is left as it is."""
    global _driver_platform_settled
    from jax._src import xla_bridge  # no public name for this question

    from .context import get_context_if_exists

    ctx = get_context_if_exists()
    if ctx is None:  # not in a cluster (yet): nothing to decide
        return
    _driver_platform_settled = True
    if ctx.is_driver and not xla_bridge.backends_are_initialized():
        jax_mod.config.update("jax_platforms", "cpu")


def _rebuild_device_array(dtype, shape, f_order, buf):
    """Inverse of the jax.Array reducer: rebuild from the (possibly
    arena-backed) out-of-band buffer. The dlpack import is zero-copy
    where XLA supports aliasing host buffers; platforms that do not
    (and readonly wire frames, and dtypes dlpack can't express, e.g.
    bfloat16) pay exactly one copy — the host->device transfer analog.
    The numpy view keeps the buffer (and through the borrow-pin ledger,
    the arena slice) alive for as long as the consumer aliases it."""
    arr = np.frombuffer(buf, dtype=dtype).reshape(
        shape, order="F" if f_order else "C")
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:  # consumer process never imported jax
        try:
            import jax as jax_mod  # noqa: F811
        except ImportError:  # pragma: no cover — cpu-only consumer
            return arr
    if not _driver_platform_settled:
        _settle_driver_platform(jax_mod)
    try:
        return jax_mod.numpy.from_dlpack(arr)
    except (BufferError, TypeError, ValueError, RuntimeError):
        # readonly buffer / dtype outside the dlpack spec: one copy
        return jax_mod.numpy.asarray(arr)


def _rebuild_host_array(dtype, shape, f_order, buf):
    return np.frombuffer(buf, dtype=dtype).reshape(
        shape, order="F" if f_order else "C")


def _payload_buffer(host: "np.ndarray") -> pickle.PickleBuffer:
    """Zero-copy byte view of a contiguous array's memory. Exported as
    flat uint8: dtypes outside the buffer-protocol spec (bfloat16 and
    friends — 'cannot include dtype in a buffer') carry their type in
    frame 0's dtype arg instead, and the rebuild's np.frombuffer
    interprets raw bytes under any registered dtype."""
    f_order = host.flags.f_contiguous and not host.flags.c_contiguous
    flat = host.reshape(-1, order="F" if f_order else "C")
    return pickle.PickleBuffer(flat.view(np.uint8))


def _device_reduce(obj):
    """Typed reducer for device arrays (and large non-contiguous host
    arrays); None delegates to the default pickling path. Gated by
    ``serialization_device_zero_copy`` (the bench A/B control)."""
    from .config import get_config

    if not get_config().serialization_device_zero_copy:
        return None
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None and isinstance(obj, jax_mod.Array):
        try:
            host = np.asarray(obj)
            if not (host.flags.c_contiguous or host.flags.f_contiguous):
                host = np.ascontiguousarray(host)
            return (_rebuild_device_array,
                    (host.dtype, host.shape,
                     bool(host.flags.f_contiguous
                          and not host.flags.c_contiguous),
                     _payload_buffer(host)))
        except Exception:  # noqa: BLE001 — non-addressable shards,
            return None    # exotic dtypes: the default path still works
    if type(obj) is np.ndarray and obj.nbytes >= _NDARRAY_OOB_MIN_BYTES \
            and not (obj.flags.c_contiguous or obj.flags.f_contiguous):
        # stock pickle5 already ships contiguous ndarrays out-of-band;
        # strided views would go IN-BAND via tobytes() — normalize once
        # and ship the contiguous copy out-of-band instead
        try:
            host = np.ascontiguousarray(obj)
            return (_rebuild_host_array,
                    (host.dtype, host.shape, False,
                     _payload_buffer(host)))
        except Exception:  # noqa: BLE001
            return None
    return None


class _RefCollectingPickler(cloudpickle.CloudPickler):
    """Module-level pickler subclass: defining this class INSIDE
    serialize() (the old shape) cost ~20 us of class creation per call
    — the dominant cost of serializing a small task result."""

    def __init__(self, file, buffer_callback, contained_refs):
        super().__init__(file, protocol=5,
                         buffer_callback=buffer_callback)
        self._contained_refs = contained_refs

    def persistent_id(self, obj):
        return None

    def reducer_override(self, obj):
        if isinstance(obj, _ref_cls):
            self._contained_refs.append(obj)
            return (_ref_cls._deserialize, (obj.id.binary(), obj.owner))
        r = _device_reduce(obj)
        if r is not None:
            return r
        # delegate (NOT NotImplemented): cloudpickle's own
        # reducer_override is what pickles closures/lambdas by value
        return super().reducer_override(obj)


# Types that can never contain an ObjectRef or need out-of-band
# buffers: stock-pickled in one shot, skipping the BytesIO +
# CloudPickler machinery entirely (a no-op task's `return 0` is THE
# common small result at high task rates).
_SCALAR_TYPES = (type(None), bool, int, float)


def serialize(value: Any) -> SerializedValue:
    t = type(value)
    if t in _SCALAR_TYPES or (t is bytes or t is str) and len(value) < 8192:
        return SerializedValue([pickle.dumps(value, protocol=5)], [])
    global _ref_cls
    if _ref_cls is None:
        from .object_ref import ObjectRef as _ref_cls_  # noqa: N813

        _ref_cls = _ref_cls_
    buffers: List[pickle.PickleBuffer] = []
    contained_refs: List[Any] = []
    sio = io.BytesIO()
    p = _RefCollectingPickler(sio, buffers.append, contained_refs)
    p.dump(value)
    # getbuffer(), not getvalue(): the pickle stream stays a zero-copy
    # view of the BytesIO's internal buffer. For in-band-heavy values
    # (bytes/str payloads) getvalue() was a full second traversal of the
    # data before the store copy even started.
    frames = [sio.getbuffer()]
    for b in buffers:
        frames.append(b.raw())
    return SerializedValue(frames, contained_refs)


def deserialize(frames: List) -> Any:
    return pickle.loads(frames[0], buffers=frames[1:])


def dumps(value: Any) -> bytes:
    """One-shot in-band serialization (for control messages)."""
    return cloudpickle.dumps(value, protocol=5)


def loads(data: bytes) -> Any:
    return pickle.loads(data)
