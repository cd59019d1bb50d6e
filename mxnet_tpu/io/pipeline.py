"""Multi-core record→decode→batch pipeline (ISSUE 7 tentpole).

The single-process ``ImageRecordIter`` tops out at one core's native JPEG
decode rate (~650 img/s builder-measured vs the 1500 img/s multi-core
target); the reference keeps this path fed with a C++ decode THREAD
pool (src/io/iter_image_recordio_2.cc), and DALI/tf.data reach the same
end with process/stream parallelism.  This module is that stage for the
TPU rebuild, built from three pieces:

- **shared-memory batch slabs** (``_Slab``): each in-flight batch owns a
  ``multiprocessing.shared_memory`` segment sized ``slots × C×H×W``
  float32 plus a label lane.  Decode workers write pixels straight into
  the slab — the native ``jpg_decode_crop_norm`` C pass takes the slot
  pointer as its output buffer — so the worker→parent return path moves
  ZERO image bytes through pickle; a task ack is ``(n, seconds)``.
- **a persistent decode pool + ordered chunk scheduler**
  (``PooledDecodePipeline``): each batch splits into record chunks fanned
  over N worker processes; workers ``pread`` record spans from their own
  file descriptor (payload offsets resolved once by the parent's native
  framing scan — ``recordio.payload_spans``).  Batch composition is
  BIT-IDENTICAL to single-process decode: same records in the same
  slots, and every record's augmentation draws come from a
  ``RandomState`` seeded per (epoch, stream index) (``io._mix_seed``),
  not from whichever worker happened to decode it.
- **double-buffered prefetch with a background assembler**:
  ``MXNET_IO_PREFETCH`` batches decode ahead of the consumer, and a
  single assembler THREAD (GIL-free in its hot ops: future waits,
  ``np.copyto``, the ctypes decode) collects finished slabs, copies them
  into private batch buffers, and recycles the slab — so the batch a
  consumer receives is already materialized and the per-``next_batch``
  consumer cost is just the device upload.  The slab→private copy exists
  because ``jax.device_put`` zero-copy-aliases page-aligned host buffers
  on CPU backends: handing a slab view to jax would alias memory the
  pipeline is about to let workers overwrite.

Failure semantics reuse the DataLoader degradation ladder (ISSUE 3): a
dead/hung worker triggers ONE failure episode — the pool is hard-killed
(a merely-hung worker could otherwise wake up and scribble on a recycled
slab), every affected chunk is re-decoded in-process from the same seeds
(so nothing is dropped or duplicated), and the pool is rebuilt — until
``MXNET_DATALOADER_RETRIES`` episodes are spent, after which decode
degrades permanently to single-process.  Chaos site ``io.decode`` fires
inside the WORKER (env-armed), so worker-kill recovery is CI-testable.

No jax anywhere in this module: the pipeline is pure numpy + stdlib (+
the ctypes native decoder), and hands the consumer numpy views.
"""

from __future__ import annotations

import os
import struct
import threading
import time
import warnings
from collections import deque
from multiprocessing import shared_memory

import numpy as _np

from .. import config
from .. import telemetry as _tel
from ..base import MXNetError

__all__ = ["PooledDecodePipeline"]

_M_DECODED = _tel.counter(
    "mxnet_io_decoded_images_total",
    "Images decoded by the io pipeline (pooled workers + in-process "
    "fallback).")
_M_DECODE_SECONDS = _tel.histogram(
    "mxnet_io_decode_seconds",
    "Decode-worker seconds per chunk (pread + JPEG decode + augment into "
    "the shared-memory slab).")
_M_QUEUE_DEPTH = _tel.gauge(
    "mxnet_io_queue_depth",
    "Batches in flight in the decode pipeline (issued to workers, not "
    "yet consumed).")

_REC_MAGIC = 0xced7230a


# --------------------------------------------------------------------------
# worker side (runs in forkserver/spawn children)
# --------------------------------------------------------------------------

_W_CFG = None
_W_FD = -1
_W_SLABS: dict = {}


def _worker_init(cfg, chaos_spec):
    """Decode-worker bring-up: store cfg, arm chaos deterministically.

    Chaos is re-armed from the spec the PARENT resolved, not from this
    process's inherited environment — a forkserver started before the
    test set ``MXNET_CHAOS_SITES`` would otherwise hand workers a stale
    environment."""
    global _W_CFG, _W_FD
    _W_CFG = cfg
    _W_FD = -1
    try:
        import cv2
        cv2.setNumThreads(1)   # one image per task; the pool is the fanout
    except Exception:  # noqa: BLE001
        pass
    from ..resilience import chaos
    chaos.clear()
    if chaos_spec:
        chaos.arm_from_spec(chaos_spec)


def _worker_fd():
    global _W_FD
    if _W_FD < 0:
        _W_FD = os.open(_W_CFG["rec_path"], os.O_RDONLY)
    return _W_FD


def _attach_slab(name):
    """numpy views over a parent-created slab, cached per worker.  Attach
    (create=False) does not register with the resource tracker — the
    parent owns the unlink."""
    views = _W_SLABS.get(name)
    if views is None:
        # NOTE: CPython < 3.13 registers ATTACHED segments with the
        # resource tracker too (bpo-39959).  Pool children inherit the
        # PARENT'S tracker, whose cache is a set — the duplicate register
        # is absorbed and the parent's destroy()/unlink stays the sole
        # owner of cleanup, so no unregister gymnastics here.
        shm = shared_memory.SharedMemory(name=name)
        views = (shm,) + _slab_views(shm, _W_CFG["slots"],
                                     _W_CFG["data_shape"])
        _W_SLABS[name] = views
    return views[1], views[2]


def _read_payload(fd, off, length):
    """One record's payload bytes.  length >= 0: exact payload span (from
    the native framing scan).  length < 0: ``off`` is the RECORD start —
    parse the magic/length framing here (native scanner unavailable)."""
    if length >= 0:
        return os.pread(fd, int(length), int(off))
    hdr = os.pread(fd, 8, int(off))
    if len(hdr) < 8:
        raise MXNetError("decode worker: truncated record header")
    magic, lrec = struct.unpack("<II", hdr)
    if magic != _REC_MAGIC:
        raise MXNetError(f"decode worker: bad record magic {magic:#x}")
    return os.pread(fd, lrec & ((1 << 29) - 1), int(off) + 8)


def _decode_chunk(slab_name, start_slot, recs):
    """Decode ``recs = [(offset, length, seed), ...]`` into the slab at
    ``start_slot..`` — the pool task body.  Returns a tiny ack (count,
    seconds, counter deltas); the image bytes never cross the process
    boundary.  The deltas leg is the worker's telemetry export channel
    (ISSUE 10): whatever counters moved in this worker since its last ack
    (chaos faults, resilience events) ride back to the parent's registry
    instead of dying with the pool."""
    from ..resilience import chaos
    if chaos._ACTIVE:
        chaos.hit("io.decode")
    from .io import _decode_record
    cfg = _W_CFG
    imgs, labels = _attach_slab(slab_name)
    fd = _worker_fd()
    t0 = time.perf_counter()
    for i, (off, length, seed) in enumerate(recs):
        raw = _read_payload(fd, off, length)
        rng = _np.random.RandomState(seed)
        slot = start_slot + i
        _, label = _decode_record(raw, cfg, rng, out=imgs[slot])
        labels[slot] = label
    return (len(recs), time.perf_counter() - t0,
            _tel.aggregate.counter_deltas())


# --------------------------------------------------------------------------
# parent side
# --------------------------------------------------------------------------

def _slab_views(shm, slots, data_shape):
    img_bytes = slots * int(_np.prod(data_shape)) * 4
    imgs = _np.ndarray((slots,) + tuple(data_shape), _np.float32,
                       buffer=shm.buf)
    labels = _np.ndarray((slots,), _np.float32, buffer=shm.buf,
                         offset=img_bytes)
    return imgs, labels


class _Slab:
    """One batch's shared-memory backing: ``slots`` CHW float32 images +
    labels.  Created (and eventually unlinked) by the parent; workers
    attach by name."""

    def __init__(self, slots, data_shape):
        size = slots * int(_np.prod(data_shape)) * 4 + slots * 4
        self.shm = shared_memory.SharedMemory(create=True, size=size)
        self.name = self.shm.name
        self.imgs, self.labels = _slab_views(self.shm, slots, data_shape)

    def destroy(self):
        # views hold exported buffer pointers; drop them before close()
        self.imgs = self.labels = None
        # unlink FIRST and independently: close() raises BufferError while
        # any view is still exported (an assembler that outlived close()'s
        # bounded join), and an unlink skipped on that path would strand
        # the tmpfs segment until process exit.  Unlinking only removes
        # the name — live mappings keep the memory valid.
        try:
            self.shm.unlink()
        except FileNotFoundError:   # already gone
            pass
        try:
            self.shm.close()
        except BufferError:         # stale view still exported; unmaps at GC
            pass


class _Entry:
    """One in-flight batch: its slab lease + the chunk work items."""

    __slots__ = ("slab", "n", "chunks")

    def __init__(self, slab, n, chunks):
        self.slab = slab          # index into the pipeline's slab list
        self.n = n                # records in this batch (<= slots)
        # [(start_slot, recs, future-or-None, pool-gen-at-submit)] — gen is
        # per CHUNK, not per entry: a batch can span a pool kill/rebuild
        # inside one _issue call, leaving dead-pool and live-pool futures
        # in the same entry
        self.chunks = chunks


class PooledDecodePipeline:
    """Ordered multi-process decode with shared-memory assembly and
    double-buffered prefetch (module docstring has the full story).

    Drive it with ``begin(schedule)`` — the epoch's ``[(keys, seeds),
    ...]`` batch plan — then ``next_batch()`` per batch, which returns
    ``(images, labels)`` PRIVATE float32 numpy arrays, materialized
    ahead of time by the assembler thread (the caller owns them; no
    lifetime contract).  ``drain()`` parks the pipeline between epochs
    without losing the worker pool; ``close()`` tears everything down.

    Locking: every mutation of scheduler state (slab free list, queues,
    pool generation/ladder) happens under ``_lock``; the assembler never
    holds it across a blocking wait, a copy, or a decode.
    """

    def __init__(self, rec, cfg, workers, slots, prefetch=None, chunk=None,
                 timeout_s=None, retries=None):
        self._rec = rec                     # parent-side reader (spans)
        self._cfg = dict(cfg)
        self._cfg["slots"] = int(slots)
        self._slots = int(slots)
        self._workers = max(1, int(workers))
        self._prefetch = max(1, int(prefetch if prefetch is not None
                             else config.get_int("MXNET_IO_PREFETCH", 2)))
        chunk = int(chunk if chunk is not None
                    else config.get_int("MXNET_IO_CHUNK", 0))
        # auto chunk: one task wave per batch (fewer, larger tasks beat
        # finer slicing on measured throughput — task pickling/IPC is the
        # marginal cost); a straggler's latency hides behind the NEXT
        # prefetched batch's chunks, which are already queued to the pool
        self._chunk = chunk if chunk > 0 else max(
            1, -(-self._slots // self._workers))
        self._timeout = float(timeout_s if timeout_s is not None
                              else config.get_float("MXNET_IO_TIMEOUT_S", 60))
        self._retries = int(retries if retries is not None
                            else config.get_int("MXNET_DATALOADER_RETRIES", 2))
        shape = tuple(self._cfg["data_shape"])
        self._slabs = [_Slab(self._slots, shape)
                       for _ in range(self._prefetch + 1)]
        # one RLock + one Condition for all scheduler state: _episode may
        # fire while _issue already holds the lock, and a single condition
        # (spurious wakeups included) is simpler than three coordinated ones
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._free = list(range(len(self._slabs)))
        self._pending = deque()     # (keys, seeds) not yet issued
        self._inflight = deque()    # _Entry, consumption order
        self._ready = deque()       # materialized (imgs, labels) batches
        self._ready_bound = 2       # assembler runs this far past decode
        self._error = None          # assembler exception → re-raised
        self._epoch_gen = 0         # bumps on drain(): stale work discard
        self._busy = False          # assembler mid-entry
        self._pool = None
        self._gen = 0               # bumps on every pool kill/rebuild
        self._failures = 0          # ladder budget spent (episodes)
        self._permanent = False     # True → single-process decode forever
        self._parent_fd = -1
        self._closed = False
        self._assembler = threading.Thread(
            target=self._assemble_loop, name="mx-io-assembler", daemon=True)
        self._assembler.start()

    # -- pool lifecycle ----------------------------------------------------

    def _chaos_spec(self):
        if not config.get_bool("MXNET_CHAOS"):
            return None
        return config.get("MXNET_CHAOS_SITES", "")

    def _ensure_pool(self):
        if self._pool is not None or self._permanent:
            return self._pool
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor
        # NOT fork: the parent usually has live JAX/XLA runtime threads by
        # now, and fork-with-threads can clone held mutexes into children
        try:
            ctx = mp.get_context("forkserver")
        except ValueError:
            ctx = mp.get_context("spawn")
        self._pool = ProcessPoolExecutor(
            self._workers, mp_context=ctx, initializer=_worker_init,
            initargs=(self._cfg, self._chaos_spec()))
        return self._pool

    def _hard_kill_pool(self):
        """Kill the pool so no worker can touch a slab again.  A hung (not
        dead) worker is the dangerous case: left alive it could finish its
        stale chunk and scribble on a recycled slab.  ProcessPoolExecutor
        exposes no kill API, so reach for its process table — the only
        portable-in-practice hard stop (stable attr since 3.8)."""
        with self._lock:
            pool, self._pool = self._pool, None
            if pool is None:
                return
            self._gen += 1
        procs = list(getattr(pool, "_processes", {}).values())
        pool.shutdown(wait=False, cancel_futures=True)
        for p in procs:
            try:
                p.terminate()
            except Exception:  # noqa: BLE001 — already dead
                pass

    def _episode(self, exc):
        """One failure episode of the degradation ladder (worker death,
        hang, or decode error): kill the pool, spend budget, warn.  Chunks
        already issued re-decode in-process as they are collected."""
        with self._lock:
            if self._pool is None:
                return        # this breakage was already handled
            from .. import resilience as _res
            self._hard_kill_pool()
            self._failures += 1
            _res.record_fallback()
            permanent = self._failures > self._retries
            if permanent:
                self._permanent = True
        if permanent:
            warnings.warn(
                f"io decode pool failed {self._failures} times "
                f"(last: {exc!r}); degrading permanently to "
                "single-process decode", stacklevel=3)
        else:
            warnings.warn(
                f"io decode pool failure ({exc!r}); re-decoding affected "
                "chunks in-process and rebuilding the pool", stacklevel=3)

    # -- scheduling --------------------------------------------------------

    def begin(self, schedule):
        """Install an epoch's batch plan and start prefetching into every
        free slab."""
        with self._lock:
            if self._inflight or self._pending or self._ready or self._busy:
                raise MXNetError("pipeline.begin: epoch already in progress "
                                 "(drain() first)")
            self._pending.extend(schedule)
            self._pump()
            self._cv.notify_all()

    def _pump(self):
        """Issue pending batches into free slabs.  Lock held by caller."""
        tel_on = _tel.enabled()
        while self._free and self._pending:
            keys, seeds = self._pending.popleft()
            self._issue(keys, seeds)
        if tel_on:
            _M_QUEUE_DEPTH.set(len(self._inflight))

    def _issue(self, keys, seeds):
        n = len(keys)
        if n > self._slots:
            raise MXNetError(f"batch of {n} exceeds slab slots {self._slots}")
        slab = self._free.pop()
        offs, lens = self._rec.payload_spans(keys)
        recs = [(int(offs[i]), int(lens[i]), int(seeds[i]))
                for i in range(n)]
        chunks = []
        for s in range(0, n, self._chunk):
            part = recs[s:s + self._chunk]
            fut = None
            if not self._permanent:
                try:
                    fut = self._ensure_pool().submit(
                        _decode_chunk, self._slabs[slab].name, s, part)
                except Exception as exc:  # noqa: BLE001 — broken pool
                    self._episode(exc)
            chunks.append((s, part, fut, self._gen))
        self._inflight.append(_Entry(slab, n, chunks))

    def _inline_chunk(self, slab, start_slot, recs):
        """Parent-side decode of one chunk — the refetch rung of the
        ladder AND the permanent single-process fallback.  Identical
        pread + seeded-RNG path as the workers, so the batch bytes come
        out the same no matter who decoded them."""
        from .io import _decode_record
        if self._parent_fd < 0:
            self._parent_fd = os.open(self._cfg["rec_path"], os.O_RDONLY)
        imgs, labels = self._slabs[slab].imgs, self._slabs[slab].labels
        t0 = time.perf_counter()
        for i, (off, length, seed) in enumerate(recs):
            raw = _read_payload(self._parent_fd, off, length)
            rng = _np.random.RandomState(seed)
            slot = start_slot + i
            _, label = _decode_record(raw, self._cfg, rng, out=imgs[slot])
            labels[slot] = label
        return time.perf_counter() - t0

    def _collect(self, entry):
        """Block until every chunk of ``entry`` has landed in its slab,
        riding the ladder for any chunk whose worker failed."""
        tel_on = _tel.enabled()
        for start_slot, recs, fut, fgen in entry.chunks:
            stale = fgen != self._gen   # that chunk's pool died after issue
            if fut is not None and not stale:
                try:
                    n, dt, deltas = fut.result(self._timeout)
                    if deltas:
                        # worker counters ride the ack channel home
                        # (unconditional — chaos/resilience counters
                        # count regardless of the span flag)
                        _tel.aggregate.absorb_counter_deltas(deltas)
                    if tel_on:
                        _M_DECODED.inc(n)
                        _M_DECODE_SECONDS.observe(dt)
                    continue
                except Exception as exc:  # noqa: BLE001 — ladder, not crash
                    self._episode(exc)
            dt = self._inline_chunk(entry.slab, start_slot, recs)
            if tel_on:
                _M_DECODED.inc(len(recs))
                _M_DECODE_SECONDS.observe(dt)

    def _assemble_loop(self):
        """The assembler thread: collect the head in-flight batch, copy
        its slab into private buffers, recycle the slab, repeat.  The
        blocking work (future waits, np.copyto, the ctypes/cv2 decode of
        the ladder) all releases the GIL, so assembly genuinely overlaps
        the consumer's python."""
        while True:
            with self._lock:
                while not self._closed and (
                        not self._inflight
                        or len(self._ready) >= self._ready_bound):
                    self._cv.wait()
                if self._closed:
                    return
                entry = self._inflight.popleft()
                self._busy = True
                egen = self._epoch_gen
            imgs = labels = None
            err = None
            try:
                self._collect(entry)
                slab = self._slabs[entry.slab]
                imgs = _np.empty_like(slab.imgs[:entry.n])
                labels = _np.empty_like(slab.labels[:entry.n])
                _np.copyto(imgs, slab.imgs[:entry.n])
                _np.copyto(labels, slab.labels[:entry.n])
            except BaseException as exc:  # noqa: BLE001 — relay to consumer
                err = exc
            with self._lock:
                self._busy = False
                if err is not None:
                    self._error = err
                elif egen == self._epoch_gen:
                    self._free.append(entry.slab)
                    self._ready.append((imgs, labels))
                    self._pump()
                else:
                    # drained mid-collect: slab returns via drain()'s reset
                    pass
                if _tel.enabled():
                    _M_QUEUE_DEPTH.set(len(self._inflight))
                self._cv.notify_all()

    def next_batch(self):
        """(images, labels) of the next batch in schedule order — private
        float32 arrays the caller owns.  Raises StopIteration when the
        installed schedule is exhausted."""
        with self._lock:
            while True:
                if self._error is not None:
                    exc, self._error = self._error, None
                    raise exc
                if self._ready:
                    batch = self._ready.popleft()
                    self._cv.notify_all()   # runway slot freed
                    return batch
                if self._closed or not (self._inflight or self._pending
                                        or self._busy):
                    raise StopIteration
                self._cv.wait()

    # -- lifecycle ---------------------------------------------------------

    def drain(self):
        """Park between epochs: discard unissued and undelivered work,
        wait until no worker or assembler can touch a slab, keep the
        worker pool warm for the next begin()."""
        with self._lock:
            self._epoch_gen += 1
            self._pending.clear()
            entries = list(self._inflight)
            self._inflight.clear()
            self._cv.notify_all()
            while self._busy:          # assembler finishing a stale entry
                self._cv.wait()
            gen = self._gen
        for entry in entries:
            for _, _, fut, fgen in entry.chunks:
                # futures of a killed pool generation never complete —
                # only current-gen chunks can still be writing slabs
                if fut is not None and fgen == gen:
                    try:
                        fut.result(self._timeout)
                    except Exception:  # noqa: BLE001
                        # a failing chunk mid-drain still means the pool
                        # can't be trusted with recycled slabs
                        self._episode(RuntimeError("drain"))
        with self._lock:
            self._ready.clear()
            self._error = None
            self._free = list(range(len(self._slabs)))

    def close(self):
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._cv.notify_all()
        if self._assembler.is_alive() \
                and self._assembler is not threading.current_thread():
            self._assembler.join(timeout=self._timeout)
        self._hard_kill_pool()
        self._pending.clear()
        self._inflight.clear()
        self._ready.clear()
        for slab in self._slabs:
            slab.destroy()
        self._slabs = []
        if self._parent_fd >= 0:
            try:
                os.close(self._parent_fd)
            except OSError:
                pass
            self._parent_fd = -1

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
