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
chunk before a relay link had its turn.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Sequence

import jax
import numpy as np
from jax import lax

from ..obs import span
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
        self._done: Deque[Callable[[], None]] = deque()
        self._settling = False

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
            with span("dataplane.d2h_chunk"):
                src_flat: jax.Array = task.src                  # on target device
                piece = src_flat[lo:hi]
                if not route.is_direct:
                    piece = jax.device_put(piece, relay_dev)    # target -> relay (ICI)
                with span("dataplane.d2h_wait"):
                    host = np.asarray(piece)                    # relay/target -> host
                with span("dataplane.d2h_store"):
                    payload.flat[lo:hi] = host
        self._done.append(on_done)

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

    Same ``spec=``/legacy-kwarg contract as ``multipath_device_put``."""
    spec = resolve_transfer_spec("multipath_device_get", spec, legacy)
    eng = engine or make_functional_engine()
    shape, dtype = jarr.shape, np.dtype(jarr.dtype)
    out = np.empty(int(np.prod(shape)) if shape else 1, dtype=dtype)
    payload = HostPayload(flat=out, shape=shape, dtype=dtype)
    item = payload.itemsize
    eng.config.chunk_bytes = max(item, (eng.config.chunk_bytes // item) * item)
    task = eng.memcpy(
        nbytes=out.nbytes, device=target, direction=Direction.D2H,
        src=jarr.reshape(-1), dst=payload, spec=spec,
    )
    return out.reshape(shape)
