"""Input pipeline: token batches onto the mesh, prefetched.

The counterpart of the train loop's device side — the host side keeps
the chip fed:

- :class:`TokenSource` readers: an in-memory array, or a memory-mapped
  token file (the flat uint16/int32 next-token-prediction corpus
  layout), sliced into (batch, seq+0) windows deterministically by
  step index, so every dp rank computes ITS slice of every global
  batch without coordination (rank r takes rows [r·b/dp, (r+1)·b/dp)).
- :func:`prefetch`: a double-buffered iterator that `device_put`s the
  NEXT global batch (with its dp sharding) while the current step
  computes — host→device transfer rides under the train step instead
  of serializing after it.

Everything is deterministic in (seed, step): resuming from a
checkpoint's step counter reproduces the exact batch stream, which is
what ties this to ckpt/ restart (no loader state to snapshot beyond
the step).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Iterator, Optional

import numpy as np

__all__ = ["TokenSource", "ArraySource", "MemmapSource", "prefetch",
           "batches"]


class TokenSource:
    """Deterministic (seed, step) → (batch, seq) int32 token windows."""

    def batch(self, step: int, batch: int, seq: int) -> np.ndarray:
        raise NotImplementedError


class ArraySource(TokenSource):
    """Windows over an in-memory 1-D token array (wraps around)."""

    def __init__(self, tokens: np.ndarray, seed: int = 0):
        self.tokens = np.ascontiguousarray(tokens.reshape(-1))
        if self.tokens.size < 2:
            raise ValueError("need at least 2 tokens")
        self.seed = seed

    def batch(self, step: int, batch: int, seq: int) -> np.ndarray:
        n = self.tokens.size
        rng = np.random.default_rng((self.seed, step))
        starts = rng.integers(0, n, size=batch)
        idx = (starts[:, None] + np.arange(seq)[None, :]) % n
        return self.tokens[idx].astype(np.int32)


class MemmapSource(ArraySource):
    """Windows over a flat binary token file via np.memmap — the corpus
    never loads into RAM; page cache serves the hot windows."""

    def __init__(self, path: str, dtype=np.uint16, seed: int = 0):
        size = os.path.getsize(path) // np.dtype(dtype).itemsize
        mm = np.memmap(path, dtype=dtype, mode="r", shape=(size,))
        # note: keep the memmap (no ascontiguousarray copy)
        self.tokens = mm
        self.seed = seed
        if size < 2:
            raise ValueError(f"{path}: too few tokens ({size})")


def batches(source: TokenSource, batch: int, seq: int,
            start_step: int = 0) -> Iterator[np.ndarray]:
    """Endless deterministic batch stream from ``start_step``."""
    step = start_step
    while True:
        yield source.batch(step, batch, seq)
        step += 1


def prefetch(it: Iterator[np.ndarray], mesh=None, spec=None,
             depth: int = 2) -> Iterator:
    """Double-buffered device prefetch.

    A daemon thread pulls host batches from ``it`` and ``device_put``s
    them (with ``NamedSharding(mesh, spec)`` when given — normally
    ``P("dp", None)``), keeping up to ``depth`` batches in flight so
    the H2D transfer of step k+1 overlaps step k's compute.  Yields
    device arrays in order.

    The iterator counts what it hands out: ``stats()`` returns
    ``{"batches": n, "starved": n, "wait_s": s}``, ``starved`` being the
    ``next`` calls that found the queue empty (the consumer then waits for
    the worker: the host sets the pace) and ``wait_s`` the seconds all of
    them spent taking a batch from the queue.  Each batch the worker makes
    is a ``data.produce`` span of ``core/scopes.host``: in a profile,
    ``ompi_tpu:data.produce``.
    """
    import jax

    from ompi_tpu.core.scopes import host

    if mesh is not None:
        from jax.sharding import NamedSharding

        sharding = NamedSharding(mesh, spec)
    else:
        sharding = None

    q: queue.Queue = queue.Queue(maxsize=depth)
    _stop = object()
    closed = threading.Event()

    def _put(item) -> bool:
        # A consumer that abandons the stream early (break/exception)
        # stops draining; a bare q.put would then block forever and pin
        # up to ``depth`` device batches in HBM for the process lifetime.
        # Poll against the closed flag so the worker exits instead.
        while not closed.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker() -> None:
        try:
            source = iter(it)
            while True:
                with host("data.produce"):
                    try:
                        host_batch = next(source)
                    except StopIteration:
                        break
                    dev = (jax.device_put(host_batch, sharding)
                           if sharding is not None
                           else jax.device_put(host_batch))
                if not _put(dev):
                    return
            _put(_stop)
        except BaseException as e:  # noqa: BLE001 — must reach consumer
            # a swallowed source/transfer error would read as a clean
            # end-of-stream; re-raise it on the consumer thread instead
            _put(e)

    t = threading.Thread(target=worker, daemon=True,
                         name="ompi-tpu-prefetch")
    t.start()

    class _PrefetchIter:
        """Iterator (not a generator): ``close`` must release the worker
        even when called before the first ``next`` or via GC — a
        generator's finally never runs if it was never started."""

        def __init__(self):
            self.batches = self.starved = 0     # consumer thread only
            self.wait_s = 0.0

        def __iter__(self):
            return self

        def __next__(self):
            if closed.is_set():
                raise StopIteration
            self.starved += q.empty()
            start = time.perf_counter()
            item = q.get()
            self.wait_s += time.perf_counter() - start
            if item is _stop:
                self.close()
                raise StopIteration
            if isinstance(item, BaseException):
                self.close()
                raise item
            self.batches += 1
            return item

        def stats(self) -> dict:
            return {"batches": self.batches, "starved": self.starved,
                    "wait_s": self.wait_s}

        def close(self, _empty=queue.Empty) -> None:
            # release the worker and drop any buffered device batches.
            # queue.Empty is bound at definition time: __del__ may run at
            # interpreter shutdown after module globals are cleared.
            closed.set()

            def drain() -> None:
                try:
                    while True:
                        q.get_nowait()
                except _empty:
                    pass

            drain()
            # a worker mid-q.put slips one item past the first drain
            # (the drain frees the slot its blocked put then fills);
            # wait for it to observe `closed` and drain again
            t.join(timeout=2.0)
            drain()

        __del__ = close

    return _PrefetchIter()


def train_stream(source: TokenSource, mesh, batch: int, seq: int,
                 start_step: int = 0, depth: int = 2,
                 spec: Optional[object] = None) -> Iterator:
    """The one-call composition: deterministic batches → dp-sharded
    device prefetch (resume by passing the checkpointed step)."""
    from ompi_tpu.core.scopes import host

    with host("build.stream"):
        from jax.sharding import PartitionSpec as P

        return prefetch(batches(source, batch, seq, start_step), mesh,
                        spec if spec is not None else P("dp", None),
                        depth=depth)
