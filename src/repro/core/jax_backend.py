"""Functional multipath backend over real ``jax`` devices.

Validates the MMA *data plane* — chunk math, route construction, relay
forwarding, distributed completion, reassembly ordering — with actual
arrays. Devices are whatever ``jax.devices()`` provides (CPU devices in
this container, TPU chips on real hardware): a direct chunk is a single
``device_put`` to the target; a relay chunk is ``device_put`` to the relay
device followed by a device-to-device ``device_put`` to the target —
exactly the paper's PCIe-then-NVLink two-hop, expressed in JAX.

Timing claims come from the simulator backend; this backend asserts
bit-exactness and exercises the Sync Engine with real threads.

Each copy is issued when its chunk is pulled, but the completion is held
until the dispatch round that pulled it has asked every link
(``settle``): delivered inline, it would let the first link pull every
chunk before a relay link had its turn. A D2H chunk's host copy starts
at its pull (``copy_to_host_async``) and is landed, waited on and stored
into the payload, when ``settle`` reaches its completion; so with a
queue depth of 2 the next chunk's copy is in flight while this one is
waited on and stored.

A D2H payload comes from the backend's ``HostBlockCache``: the next sleep
of the same sizes stores into pages the last one already faulted in.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
import weakref
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax import lax

from ..obs import count, span
from .config import MMAConfig
from .engine import MMAEngine
from .path_selector import Route
from .task_launcher import Backend
from .topology import Device, Topology
from .transfer_task import (
    Direction,
    MicroTask,
    TrafficClass,
    TransferSpec,
    TransferTask,
    resolve_transfer_spec,
)


@dataclasses.dataclass
class HostPayload:
    """Flat host-side view of the transfer source/destination."""

    flat: np.ndarray            # 1-D view, dtype preserved
    shape: tuple
    dtype: np.dtype

    @property
    def itemsize(self) -> int:
        return self.flat.dtype.itemsize


class _Lease:
    """The root of every array made from one lent block: numpy collapses
    view bases onto it and a zero-copy ``device_put`` holds it, so it is
    collected only once nothing references the block's memory. (A
    ``memoryview`` cannot be the root: ``np.frombuffer`` wraps it in a new
    one and drops it at once.)"""

    __slots__ = ("__array_interface__", "block", "__weakref__")

    def __init__(self, block: np.ndarray) -> None:
        self.block = block
        self.__array_interface__ = {
            "data": (block.ctypes.data, False), "shape": (block.nbytes,),
            "typestr": "|u1", "version": 3,
        }


def _give_back(cache_ref: "weakref.ref[HostBlockCache]",
               block: np.ndarray) -> None:
    cache = cache_ref()
    if cache is not None:
        cache.give_back(block)


class HostBlockCache:
    """Caching host allocator for D2H payloads.

    A fresh ``np.empty`` of a leaf's size is mmap'd, and the store into it
    faults its pages in 4 KiB at a time; reused pages take the store at copy
    speed. Blocks are binned by exact byte size: ``take`` serves a payload
    from its bin (a hit) or allocates (a miss). A block goes back to its bin
    when its ``_Lease`` is collected, i.e. when no array, view or zero-copy
    device buffer made from it is left, so there is no ``release()`` to
    misuse. Free bytes kept never exceed the most bytes ever lent at once; a
    block that would pass that mark is dropped. Blocks lent out when the
    cache is collected are freed with their last reference.
    """

    def __init__(self) -> None:
        self._free: Dict[int, List[np.ndarray]] = {}
        # Blocks come back on any thread, and on this one from a garbage
        # collection that an allocation under the lock sets off.
        self._lock = threading.RLock()
        self.free_bytes = 0
        self.lent_bytes = 0
        self.peak_lent_bytes = 0

    def take(self, nbytes: int, dtype: np.dtype) -> Tuple[np.ndarray, bool]:
        """A 1-D ``dtype`` array over ``nbytes`` of host memory, and whether
        it came from the cache."""
        with self._lock:
            bin_ = self._free.get(nbytes)
            block = bin_.pop() if bin_ else None
            if block is not None:
                self.free_bytes -= nbytes
            self.lent_bytes += nbytes
            self.peak_lent_bytes = max(self.peak_lent_bytes, self.lent_bytes)
        reused = block is not None
        if block is None:
            block = np.empty(nbytes, np.uint8)
        lease = _Lease(block)
        weakref.finalize(lease, _give_back, weakref.ref(self), block).atexit = False
        return np.asarray(lease).view(dtype), reused

    def give_back(self, block: np.ndarray) -> None:
        n = block.nbytes
        with self._lock:
            self.lent_bytes -= n
            if self.free_bytes + n <= self.peak_lent_bytes:
                self._free.setdefault(n, []).append(block)
                self.free_bytes += n


class ChunkAssembler:
    """Collects landed chunks and reassembles the logical payload."""

    def __init__(self, n_chunks: int, target_device) -> None:
        self.chunks: Dict[int, jax.Array] = {}
        self.n_chunks = n_chunks
        self.target_device = target_device

    def add(self, seq: int, chunk: jax.Array) -> None:
        self.chunks[seq] = chunk

    def complete(self) -> bool:
        return len(self.chunks) == self.n_chunks

    def result(self, shape, dtype) -> jax.Array:
        """The payload, once. The parts are handed over and joined in one
        concatenation (``jnp.concatenate`` builds a tree of them, a third
        payload-sized buffer), so device memory peaks at the parts plus
        the payload."""
        parts = [self.chunks.pop(i) for i in range(self.n_chunks)]
        out = lax.concatenate(parts, 0) if len(parts) > 1 else parts[0]
        del parts
        return out.reshape(shape).astype(dtype)


class JaxBackend(Backend):
    def __init__(self, devices: Optional[Sequence] = None) -> None:
        self.devices = list(devices if devices is not None else jax.devices())
        self.host_blocks = HostBlockCache()
        self._done: Deque[Callable[[], None]] = deque()
        self._settling = False
        self._d2h_issued = 0    # D2H copies started so far

    def now(self) -> float:
        return time.monotonic()

    def launch(
        self, mt: MicroTask, route: Route, on_done: Callable[[], None]
    ) -> None:
        # Copies are issued here and cannot be recalled, so no
        # PreemptHandle is returned (preemption is a sim-backend feature).
        task = mt.parent
        payload: HostPayload = (
            task.src if mt.direction == Direction.H2D else task.dst
        )
        itemsize = payload.itemsize
        assert mt.offset % itemsize == 0 and mt.nbytes % itemsize == 0, (
            "chunk boundaries must be element-aligned"
        )
        lo = mt.offset // itemsize
        hi = lo + mt.nbytes // itemsize
        target_dev = self.devices[route.dest]
        relay_dev = self.devices[route.link_dev]

        if mt.direction == Direction.H2D:
            with span("dataplane.h2d_chunk"):
                view = payload.flat[lo:hi]
                if route.is_direct:
                    chunk = jax.device_put(view, target_dev)    # host -> target
                else:
                    staged = jax.device_put(view, relay_dev)    # host -> relay (PCIe)
                    chunk = jax.device_put(staged, target_dev)  # relay -> target (ICI)
            assembler: ChunkAssembler = task.dst
            assembler.add(mt.seq, chunk)
        else:
            with span("dataplane.d2h_issue"):
                src_flat: jax.Array = task.src                  # on target device
                piece = src_flat[lo:hi]
                if not route.is_direct:
                    piece = jax.device_put(piece, relay_dev)    # target -> relay (ICI)
                piece.copy_to_host_async()                      # relay/target -> host
            self._d2h_issued += 1
            on_done = functools.partial(
                self._land, piece, payload.flat[lo:hi], self._d2h_issued,
                on_done)
        self._done.append(on_done)

    def _land(self, piece: jax.Array, dst: np.ndarray, issued: int,
              on_done: Callable[[], None]) -> None:
        """Land one D2H chunk, the ``issued``-th copy started: wait for its
        copy, store it into ``dst`` (its span of the payload) and deliver
        its completion, which on a link with room issues the next chunk."""
        with span("dataplane.d2h_chunk"):
            # 1 when a later copy is in flight; a 0 still makes the counter,
            # which tells a landing with none from a program without it.
            count("dataplane.d2h_overlapped", int(self._d2h_issued > issued))
            with span("dataplane.d2h_wait"):
                host = np.asarray(piece)
            with span("dataplane.d2h_store"):
                dst[...] = host
            on_done()

    def settle(self) -> None:
        """Deliver held completions until none is left; each may pull and
        launch more chunks. Re-entrant calls return at once."""
        if self._settling:
            return
        self._settling = True
        try:
            while self._done:
                self._done.popleft()()
        finally:
            self._settling = False


def _functional_topology(n_devices: int) -> Topology:
    """Degenerate topology for the functional backend (rates unused)."""
    return Topology(
        devices=[Device(i, 0) for i in range(n_devices)],
        pcie_gbps=1.0, nvlink_gbps=1.0, dram_gbps=1.0, xgmi_gbps=1.0,
        chunk_overhead_s=0.0, name="functional",
    )


def make_functional_engine(
    devices: Optional[Sequence] = None,
    config: Optional[MMAConfig] = None,
) -> MMAEngine:
    backend = JaxBackend(devices)
    cfg = config or MMAConfig(chunk_bytes=1 << 20, fallback_bytes=0)
    topo = _functional_topology(len(backend.devices))
    return MMAEngine(topo, backend, cfg)


# ---------------------------------------------------------------------------
# Public helpers: the MMA-accelerated device_put / device_get
# ---------------------------------------------------------------------------
def multipath_device_put(
    arr: np.ndarray,
    target: int = 0,
    engine: Optional[MMAEngine] = None,
    spec: Optional[TransferSpec] = None,
    **legacy,
) -> jax.Array:
    """H2D: move a host array to ``devices[target]`` over all paths.

    Policy rides in ``spec=TransferSpec(...)``; the legacy loose
    ``traffic_class=``/``tenant=`` kwargs still work but emit a
    ``repro.``-prefixed DeprecationWarning."""
    spec = resolve_transfer_spec("multipath_device_put", spec, legacy)
    eng = engine or make_functional_engine()
    payload = HostPayload(
        flat=np.ascontiguousarray(arr).reshape(-1), shape=arr.shape,
        dtype=arr.dtype,
    )
    backend: JaxBackend = eng.backend  # type: ignore[assignment]
    # Element-align the chunk size.
    item = payload.itemsize
    eng.config.chunk_bytes = max(item, (eng.config.chunk_bytes // item) * item)
    assembler = ChunkAssembler(
        eng.config.n_chunks(arr.nbytes), backend.devices[target]
    )
    task = eng.memcpy(
        nbytes=arr.nbytes, device=target, direction=Direction.H2D,
        src=payload, dst=assembler, spec=spec,
    )
    assert assembler.complete(), "functional dispatch must complete inline"
    return assembler.result(payload.shape, payload.dtype)


def multipath_device_get(
    jarr: jax.Array,
    target: int = 0,
    engine: Optional[MMAEngine] = None,
    spec: Optional[TransferSpec] = None,
    **legacy,
) -> np.ndarray:
    """D2H: fetch a device array back to host memory over all paths.

    The array comes from ``engine``'s host block cache (``HostBlockCache``);
    its block is reused once the array and all views of it are gone. Same
    ``spec=``/legacy-kwarg contract as ``multipath_device_put``."""
    spec = resolve_transfer_spec("multipath_device_get", spec, legacy)
    eng = engine or make_functional_engine()
    backend: JaxBackend = eng.backend  # type: ignore[assignment]
    shape, dtype = jarr.shape, np.dtype(jarr.dtype)
    size = int(np.prod(shape)) if shape else 1
    out, reused = backend.host_blocks.take(size * dtype.itemsize, dtype)
    count("dataplane.d2h_payload")
    if reused:
        count("dataplane.d2h_payload_reused")
    payload = HostPayload(flat=out, shape=shape, dtype=dtype)
    item = payload.itemsize
    eng.config.chunk_bytes = max(item, (eng.config.chunk_bytes // item) * item)
    task = eng.memcpy(
        nbytes=out.nbytes, device=target, direction=Direction.D2H,
        src=jarr.reshape(-1), dst=payload, spec=spec,
    )
    return out.reshape(shape)
