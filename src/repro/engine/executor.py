"""Sharded, double-buffered scoring executor — the engine's hot path.

ScaleDoc's online phase assumes proxy scoring is effectively free next
to LLM calls; that only holds if the full-collection scan is limited by
hardware, not by Python. This executor turns every scoring pass into a
three-stage streaming pipeline:

    chunk k+1: host read + device_put   (background prefetch thread)
    chunk k:   device compute           (fused kernel / jnp / shard_map)
    chunk k-1: host write of scores

Stages overlap: while the device scores chunk *k*, the prefetch thread
is already paging chunk *k+1* off the ``DocumentStore`` (disk for
``MemmapStore``) and transferring it, so host I/O hides behind compute
(classic double buffering — the queue depth bounds resident chunks).

Three compute paths, chosen per call:

  * ``jnp``    — single device, the same jitted chunk programs as
    repro.core.scoring. This is the default and is **bit-identical** to
    the PR-1 scoring path: same chunk boundaries, same XLA programs.
  * ``fused``  — ``use_kernel=True``: the Pallas fused multi-query
    kernel (repro.kernels.fused_scoring), one MLP pass per tile for all
    Q pending query latents.
  * ``shard``  — more than one device in the mesh: document tiles are
    row-sharded over the mesh with ``shard_map``. Tiles are padded to
    divide the mesh, and the partition spec is resolved through
    repro.sharding's logical "batch" rule (so a pod×data mesh shards
    rows over both axes without executor changes). Purely
    data-parallel — no collectives — and it degrades transparently to
    the single-device path when the mesh has one device. The fused
    kernel runs on one device only, so ``use_kernel=True`` with a
    multi-device mesh is refused at construction.

Every pass returns a ``ScoringStats`` record (bytes streamed, tiles
scored, per-stage wall-clock) which the engine aggregates into
``FilterResult.scoring_stats``.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.scoring import (_iter_chunks, _num_docs,
                                _proxy_chunk_scores,
                                _proxy_chunk_scores_impl,
                                _raw_chunk_scores, _raw_chunk_scores_impl,
                                _single_chunk_scores,
                                _single_chunk_scores_impl, group_jobs)
from repro.core.encoder import encoder_apply, l2_normalize
from repro.runtime import trace as trace_mod
from repro.sharding.rules import RuleSet

DEFAULT_PREFETCH_DEPTH = 2


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScoringStats:
    """Per-stage accounting for one (or several merged) scoring passes."""
    docs_scored: int = 0
    queries_scored: int = 0
    tiles_scored: int = 0           # document chunks consumed
    bytes_streamed: int = 0         # host bytes read off the store
    host_io_seconds: float = 0.0    # prefetch thread: store read + device_put
    compute_seconds: float = 0.0    # consumer: blocked on device compute
    stall_seconds: float = 0.0      # consumer: waiting on an empty queue
    wall_seconds: float = 0.0
    devices: int = 1
    paths: Tuple[str, ...] = ()     # compute paths used ("jnp"|"fused"|"shard")

    def merge(self, other: "ScoringStats") -> "ScoringStats":
        """Accumulate another pass into this record (in place)."""
        self.docs_scored += other.docs_scored
        self.queries_scored += other.queries_scored
        self.tiles_scored += other.tiles_scored
        self.bytes_streamed += other.bytes_streamed
        self.host_io_seconds += other.host_io_seconds
        self.compute_seconds += other.compute_seconds
        self.stall_seconds += other.stall_seconds
        self.wall_seconds += other.wall_seconds
        self.devices = max(self.devices, other.devices)
        for p in other.paths:
            if p not in self.paths:
                self.paths = self.paths + (p,)
        return self

    @property
    def overlap_fraction(self) -> float:
        """How much of host I/O hid behind compute (1.0 = fully hidden)."""
        if self.host_io_seconds <= 0:
            return 1.0
        return max(0.0, 1.0 - self.stall_seconds / self.host_io_seconds)


# ---------------------------------------------------------------------------
# prefetch pipeline (_iter_chunks/_num_docs come from core.scoring so the
# executor's tile boundaries can never drift from the reference path's)
# ---------------------------------------------------------------------------

class PrefetchThread:
    """Background producer thread feeding a bounded queue ahead of a
    device-compute consumer.

    ``depth`` bounds how many items may be resident beyond the one being
    consumed (depth=2 gives classic double buffering). Exceptions in the
    producer are re-raised in the consumer; if the *consumer* dies (or
    abandons the iterator), the stop event unblocks the producer so the
    thread and its queued device buffers are released rather than pinned
    for the process lifetime. The consumer records how long it stalled
    waiting on an empty queue (perfect overlap = 0 stall); producers
    accumulate their host-side work into ``io_seconds``.

    Subclasses implement ``_produce(*args)`` (args = whatever was passed
    to ``__init__`` after ``depth``), pushing items via ``_put`` and
    returning early when it reports the consumer is gone. The scoring
    ``_Prefetcher`` and the ingest batch feeder share this lifecycle.
    Each wait on the queue is a ``wait_phase`` phase of the consumer's
    ambient span.
    """

    _DONE = object()
    wait_phase = "stall"

    def __init__(self, depth: int, *args):
        self._queue: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self.io_seconds = 0.0
        self.stall_seconds = 0.0
        self._thread = threading.Thread(target=self._run, args=args,
                                        daemon=True)
        self._thread.start()

    def _run(self, *args):
        try:
            self._produce(*args)
            self._put(self._DONE)
        except BaseException as exc:  # surfaced on the consumer side
            self._put(exc)

    def _produce(self, *args):
        raise NotImplementedError

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self):
        try:
            while True:
                with trace_mod.phase(self.wait_phase):
                    t0 = time.perf_counter()
                    item = self._queue.get()
                    self.stall_seconds += time.perf_counter() - t0
                if item is self._DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            # consumer done or dead: release the producer and any
            # still-buffered chunks
            self._stop.set()
            while True:
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break


class _Prefetcher(PrefetchThread):
    """Pages store chunks host->device ahead of the scoring compute."""

    def __init__(self, store, chunk: int, depth: int, put_fn):
        super().__init__(depth, store, chunk, put_fn)

    def _produce(self, store, chunk, put_fn):
        for start, block in _iter_chunks(store, chunk):
            if self._stop.is_set():
                return
            t0 = time.perf_counter()
            arr = np.ascontiguousarray(block, dtype=np.float32)
            dev = put_fn(arr)
            self.io_seconds += time.perf_counter() - t0
            if not self._put((start, arr.shape[0], arr.nbytes, dev)):
                return


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

class ScoringExecutor:
    """Streams a document collection through proxy scoring.

    Parameters
    ----------
    chunk:          documents per streamed tile.
    use_kernel:     route proxy groups through the fused multi-query
                    Pallas kernel (TPU; ``interpret=True`` runs it on
                    CPU for tests).
    interpret:      Pallas interpret mode (CPU testing of the kernel).
    mesh:           a ``jax.sharding.Mesh`` with a ``"data"`` axis to
                    shard document tiles over; ``None`` = single device.
    prefetch_depth: chunks the background thread may run ahead
                    (2 = double buffering; 0/1 = no lookahead).
    """

    def __init__(self, *, chunk: int = 8192, use_kernel: bool = False,
                 interpret: bool = False, mesh: Optional[Mesh] = None,
                 prefetch_depth: int = DEFAULT_PREFETCH_DEPTH):
        if use_kernel and mesh is not None and mesh.devices.size > 1:
            raise ValueError(
                "use_kernel=True scores on one device, but the mesh has "
                f"{mesh.devices.size}; pass use_kernel=False to shard "
                "tiles over the mesh, or mesh=None for the fused kernel")
        self.chunk = chunk
        self.use_kernel = use_kernel
        self.interpret = interpret
        self.mesh = mesh
        self.prefetch_depth = prefetch_depth
        self._sharded_fns: Dict[str, object] = {}

    # -- sharding helpers ---------------------------------------------------

    @property
    def _mesh_size(self) -> int:
        return 1 if self.mesh is None else self.mesh.devices.size

    def _tile_spec(self, shape) -> P:
        """Row-shard spec for a document tile, resolved through the
        logical "batch" rule (falls back to replication when the tile
        does not divide the mesh)."""
        return RuleSet(self.mesh).spec(("batch", None), shape)

    def _put(self, sharded: bool):
        if not sharded:
            return jnp.asarray
        mesh = self.mesh

        def put(arr: np.ndarray):
            pad = (-arr.shape[0]) % mesh.devices.size
            if pad:
                arr = np.pad(arr, ((0, pad), (0, 0)))
            return jax.device_put(
                arr, NamedSharding(mesh, self._tile_spec(arr.shape)))
        return put

    def _sharded_fn(self, kind: str):
        """shard_map'd twin of the single-device chunk programs. Purely
        data-parallel over rows -> no collectives in the body."""
        fn = self._sharded_fns.get(kind)
        if fn is not None:
            return fn
        mesh = self.mesh
        rows_spec = self._tile_spec((mesh.devices.size, 1))
        row = rows_spec[0] if len(rows_spec) else None
        tile2d, out2d, out1d = P(row, None), P(row, None), P(row)

        if kind == "proxy_multi":
            mapped = jax.shard_map(_proxy_chunk_scores_impl, mesh=mesh,
                                   in_specs=(P(), tile2d, P()),
                                   out_specs=out2d)
        elif kind == "raw_multi":
            mapped = jax.shard_map(_raw_chunk_scores_impl, mesh=mesh,
                                   in_specs=(tile2d, P()), out_specs=out2d)
        else:  # single
            mapped = jax.shard_map(_single_chunk_scores_impl, mesh=mesh,
                                   in_specs=(P(), tile2d, P()),
                                   out_specs=out1d)
        fn = jax.jit(mapped)
        self._sharded_fns[kind] = fn
        return fn

    # -- public API ---------------------------------------------------------

    def score(self, params, e_q, store) -> Tuple[np.ndarray, ScoringStats]:
        """One predicate over the collection -> ((N,) scores, stats).

        Default path replays repro.core.scoring.score_collection's exact
        chunk programs (bit-identical decisions); prefetch only changes
        *when* host blocks are read, never their values.
        """
        if self.use_kernel and params is not None:
            scores, stats = self.score_multi([(params, e_q)], store)
            return scores[:, 0], stats
        t0 = time.perf_counter()
        if params is None:
            z_q = l2_normalize(jnp.asarray(e_q))
        else:
            z_q = l2_normalize(encoder_apply(params, jnp.asarray(e_q)))
        sharded = self._mesh_size > 1
        pre = _Prefetcher(store, self.chunk, self.prefetch_depth,
                          self._put(sharded))
        n = _num_docs(store)
        out = np.empty((n,), np.float32)
        tiles = nbytes = 0
        compute_s = 0.0
        for start, rows, tile_bytes, dev in pre:
            with trace_mod.phase("sync"):
                tc = time.perf_counter()
                if sharded:
                    s = self._sharded_fn("single")(params, dev, z_q) \
                        if params is not None else \
                        self._sharded_fn("raw_multi")(dev,
                                                      z_q[:, None])[:, 0]
                elif params is None:
                    s = _raw_chunk_scores(dev, z_q[:, None])[:, 0]
                else:
                    s = _single_chunk_scores(params, dev, z_q)
                out[start:start + rows] = np.asarray(s, np.float32)[:rows]
                compute_s += time.perf_counter() - tc
            tiles += 1
            nbytes += tile_bytes
        stats = ScoringStats(
            docs_scored=n, queries_scored=1, tiles_scored=tiles,
            bytes_streamed=nbytes, host_io_seconds=pre.io_seconds,
            compute_seconds=compute_s, stall_seconds=pre.stall_seconds,
            wall_seconds=time.perf_counter() - t0,
            devices=self._mesh_size if sharded else 1,
            paths=("shard",) if sharded else ("jnp",))
        # ambient annotation: lands on the enclosing "score" span (the
        # engine opens one per scoring pass); no-op outside a trace. The
        # prefetch thread's read-and-copy time has no phase on this
        # thread, so it rides as an attribute
        trace_mod.annotate(io_seconds=round(pre.io_seconds, 6))
        return out, stats

    def score_multi(self, jobs: Sequence[Tuple[Optional[Dict], np.ndarray]],
                    store) -> Tuple[np.ndarray, ScoringStats]:
        """Many predicates in ONE streaming pass -> ((N, Q) scores, stats).

        jobs: sequence of (params, e_q); ``params=None`` means raw
        cosine. Jobs sharing one params object are grouped: each tile is
        encoded once per distinct proxy, and with ``use_kernel`` the
        whole group runs inside the fused multi-query Pallas kernel.
        Column order follows job order (matches
        repro.core.scoring.score_collection_multi).
        """
        n = _num_docs(store)
        if not jobs:
            return (np.zeros((n, 0), np.float32),
                    ScoringStats(docs_scored=n))
        t0 = time.perf_counter()

        # shared grouping (core.scoring.group_jobs) keeps column order
        # and grouping key in lockstep with the reference path; stacks
        # are (Q_g, latent) for the kernel path, transposed for matmul
        groups, zq_stacks = group_jobs(jobs)

        sharded = self._mesh_size > 1
        pre = _Prefetcher(store, self.chunk, self.prefetch_depth,
                          self._put(sharded))
        out = np.empty((n, len(jobs)), np.float32)
        tiles = nbytes = 0
        compute_s = 0.0
        paths = set()
        for start, rows, tile_bytes, dev in pre:
            with trace_mod.phase("sync"):
                tc = time.perf_counter()
                for (params, cols), zq in zip(groups, zq_stacks):
                    if self.use_kernel and params is not None:
                        from repro.kernels.fused_scoring import ops as sops
                        s = sops.score_tile_multi(params, zq, dev,
                                                  interpret=self.interpret)
                        paths.add("fused")
                    elif sharded:
                        if params is None:
                            s = self._sharded_fn("raw_multi")(dev, zq.T)
                        else:
                            s = self._sharded_fn("proxy_multi")(
                                params, dev, zq.T)
                        paths.add("shard")
                    elif params is None:
                        s = _raw_chunk_scores(dev, zq.T)
                        paths.add("jnp")
                    else:
                        s = _proxy_chunk_scores(params, dev, zq.T)
                        paths.add("jnp")
                    out[start:start + rows, np.asarray(cols)] = \
                        np.asarray(s, np.float32)[:rows]
                compute_s += time.perf_counter() - tc
            tiles += 1
            nbytes += tile_bytes
        stats = ScoringStats(
            docs_scored=n, queries_scored=len(jobs), tiles_scored=tiles,
            bytes_streamed=nbytes, host_io_seconds=pre.io_seconds,
            compute_seconds=compute_s, stall_seconds=pre.stall_seconds,
            wall_seconds=time.perf_counter() - t0,
            devices=self._mesh_size if sharded else 1,
            paths=tuple(sorted(paths)))
        return out, stats
