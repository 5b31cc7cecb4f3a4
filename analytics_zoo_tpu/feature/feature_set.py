"""FeatureSet — the TPU-native data-caching layer, replacing the reference's
``FeatureSet.scala`` family:

* ``CachedDistributedFeatureSet`` (``FeatureSet.scala:222-322``): per-partition
  in-memory cache + shuffled index + an *infinite looped iterator* for
  training → here an in-host-RAM numpy cache with a per-epoch reshuffled
  permutation and an infinite batch generator.
* ``DiskFeatureSet`` DRAM-slice semantics (``FeatureSet.scala:332-409``) →
  ``numpy.memmap``-backed arrays pass straight through: the OS page cache is
  the slice manager, so datasets larger than RAM stream from disk.
* factory ``FeatureSet.rdd(memoryType=...)`` (``FeatureSet.scala:423-466``) →
  ``FeatureSet.array(...)`` / ``FeatureSet.from_iterable(...)``.

TPU-critical difference from round 1's synchronous per-batch indexing: batches
are assembled on a background thread and transferred with double-buffered
``device_put`` (``prefetch_to_device``), so the chip never waits on the host —
the role Spark's per-partition parallelism plays for the reference.
"""

from __future__ import annotations

import collections
import contextlib
import queue
import threading
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel import mesh as mesh_lib
from .common import Preprocessing


def _as_list(x) -> List[np.ndarray]:
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _keep(a):
    """Host-or-device array normalization. Device-resident ``jax.Array``s
    stay on device — ``np.asarray`` would drag them back through the
    host (a full HBM→host readback and a re-upload), which matters
    for extract→fit chains where one model's jitted output feeds another
    model's training (the reference's frozen-backbone transfer-learning
    flow keeps features in executor RAM the same way,
    ``FeatureSet.scala:222-322``). Host-side batch slicing converts ONCE
    via ``_host_xs`` below, never per batch."""
    if isinstance(a, jax.Array):
        return a
    return np.asarray(a)


class FeatureSet:
    """In-memory (host-RAM) cached dataset of ``x`` (array or list of arrays)
    and optional ``y``. One instance per host process; under multi-host each
    host holds its shard of the global dataset, mirroring the reference's
    per-partition caches."""

    def __init__(self, x, y=None, shuffle: bool = True, seed: int = 0):
        self.xs = [_keep(a) for a in _as_list(x)]
        if not self.xs:
            raise ValueError("FeatureSet needs at least one feature array")
        n = self.xs[0].shape[0]
        for a in self.xs:
            if a.shape[0] != n:
                raise ValueError("feature arrays disagree on leading dim")
        self.y = None if y is None else _keep(y)
        if self.y is not None and self.y.shape[0] != n:
            raise ValueError("labels disagree with features on leading dim")
        self.shuffle = shuffle
        self.seed = seed

    # ---- factories (FeatureSet.scala:423-466) -----------------------------
    @staticmethod
    def array(x, y=None, *, shuffle: bool = True, seed: int = 0) -> "FeatureSet":
        return FeatureSet(x, y, shuffle=shuffle, seed=seed)

    @staticmethod
    def from_iterable(records: Sequence[Tuple[Any, Any]], *, shuffle: bool = True,
                      seed: int = 0) -> "FeatureSet":
        """Build from an iterable of ``(x, y)`` records (the RDD-of-Samples
        role). Stacks everything into contiguous arrays once."""
        xs, ys = [], []
        for rec in records:
            if isinstance(rec, tuple) and len(rec) == 2:
                xs.append(rec[0])
                ys.append(rec[1])
            else:
                xs.append(rec)
        x = np.stack([np.asarray(a) for a in xs])
        y = np.stack([np.asarray(a) for a in ys]) if ys else None
        return FeatureSet(x, y, shuffle=shuffle, seed=seed)

    # ---- basic protocol ---------------------------------------------------
    def __len__(self) -> int:
        return self.xs[0].shape[0]

    @property
    def x(self):
        return self.xs if len(self.xs) > 1 else self.xs[0]

    def transform(self, fn: Union[Preprocessing, Callable]) -> "FeatureSet":
        """Apply a (vectorized) preprocessing to the cached arrays — the
        ``featureSet.transform(preprocessing)`` step of the reference
        (cache-after-transform, ``FeatureSet.scala:222-322``). ``fn`` receives
        ``(x, y)`` and returns ``(x', y')``."""
        xs, y = self._host_view()
        out = fn((xs if len(xs) > 1 else xs[0], y))
        x2, y2 = out
        return FeatureSet(x2, y2, shuffle=self.shuffle, seed=self.seed)

    # ---- iterators --------------------------------------------------------
    def _host_view(self):
        """Numpy copies of device-resident arrays, materialized ONCE and
        memoized — the host slicing below must not re-read HBM per batch.
        ``xs``/``y`` are read exactly once: subclasses make them properties
        backed by full-file disk gathers (DiskFeatureSet)."""
        xs, y = self.xs, self.y
        if not any(isinstance(a, jax.Array)
                   for a in xs + ([y] if y is not None else [])):
            return xs, y
        if getattr(self, "_host_xs", None) is None:
            self._host_xs = [np.asarray(a) for a in xs]
            self._host_y = None if y is None else np.asarray(y)
        return self._host_xs, self._host_y

    def _order(self, epoch: int) -> np.ndarray:
        n = len(self)
        if not self.shuffle:
            return np.arange(n)
        return np.random.default_rng(self.seed + epoch).permutation(n)

    def _slice(self, idx) -> Tuple[Any, Any]:
        xs, y = self._host_view()
        bx = [a[idx] for a in xs]
        bx = bx if len(bx) > 1 else bx[0]
        by = None if y is None else y[idx]
        return bx, by

    def iter_batches(self, batch_size: int, *, epoch: int = 0,
                     drop_last: bool = True) -> Iterator[Tuple[Any, Any]]:
        """One pass (one 'epoch'), reshuffled by ``epoch`` number."""
        order = self._order(epoch)
        n = len(self)
        end = n - (n % batch_size) if drop_last else n
        for i in range(0, end, batch_size):
            yield self._slice(order[i:i + batch_size])

    def infinite_batches(self, batch_size: int, *, start_epoch: int = 0,
                         ) -> Iterator[Tuple[Any, Any]]:
        """The training iterator: loops forever, reshuffling every pass —
        ``CachedDistributedFeatureSet``'s infinite looped iterator
        (``FeatureSet.scala:264-322``)."""
        epoch = start_epoch
        while True:
            yield from self.iter_batches(batch_size, epoch=epoch, drop_last=True)
            epoch += 1

    def steps_per_epoch(self, batch_size: int, drop_last: bool = True) -> int:
        n = len(self)
        return n // batch_size if drop_last else (n + batch_size - 1) // batch_size

    def sample(self, n: int):
        """First ``n`` records — shape/dtype probing (e.g. lazy weight
        init) without materializing more than ``n`` rows."""
        bx = [np.asarray(a[:n]) for a in self.xs]
        return bx if len(bx) > 1 else bx[0]


# ---------------------------------------------------------------------------
# async host prefetch + double-buffered device transfer
# ---------------------------------------------------------------------------

class _ThreadedIterator:
    """Run a host iterator on a background thread with a bounded queue —
    overlaps numpy batch assembly with device compute (the reference gets
    this overlap from Spark's task threads; here it is explicit)."""

    _END = object()

    def __init__(self, it: Iterator, buffer_size: int = 4):
        self._q: "queue.Queue" = queue.Queue(maxsize=buffer_size)
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()

        def run():
            try:
                for item in it:
                    if not self._put(item):
                        return
            except BaseException as e:  # propagate to consumer
                self._err = e
            finally:
                # the sentinel is delivered UNCONDITIONALLY — a consumer
                # blocked in __next__ (or one that races close()) needs
                # the END to raise StopIteration/propagate _err rather
                # than hang. While live, wait for the consumer like any
                # item; once close() set the stop flag the stream is
                # abandoned, so freeing a slot (dropping one unread
                # item) to land the sentinel is correct and guarantees
                # termination.
                while True:
                    try:
                        self._q.put(self._END, timeout=0.1)
                        return
                    except queue.Full:
                        if self._stop.is_set():
                            try:
                                self._q.get_nowait()
                            except queue.Empty:
                                pass

        self._t = threading.Thread(target=run, daemon=True)
        self._t.start()

    def _put(self, item) -> bool:
        """Bounded producer put: re-check the stop flag between timed
        attempts so a consumer that stopped consuming mid-buffer-full
        (close() racing a refill) releases this thread instead of
        parking it forever on a full queue (ZL011)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._END:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self):
        self._stop.set()
        # drain so the producer can observe the stop flag
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass


_EXHAUSTED = object()


def prefetch_to_device(it: Iterator, mesh=None, *, buffer_size: int = 2,
                       threaded: bool = True, ledger=None,
                       phases=None) -> Iterator:
    """Double-buffered device transfer: keep ``buffer_size`` batches already
    dispatched to the devices while the current one computes. ``device_put``
    is async in JAX, so this pipeline hides both host batch assembly (via the
    background thread) and PCIe/DMA transfer behind the previous step.

    ``ledger`` (a :class:`~..observability.goodput.GoodputLedger`)
    attributes the step/data seam from inside the pipeline. Time spent
    in here — the blocking source pull plus batch assembly and transfer
    dispatch — is offered as ``data_wait``; a ledger that was given the
    loop's in-flight probe books it so only when the device had run dry
    at the interval's end, and as ``device_step`` while the device still
    had steps queued (the host was merely ahead). The consumer's time
    between a yielded batch and its next ``next()`` is the training step
    (``device_step``); spin-up before the first pull is ``idle``. The
    notes run on the consumer's thread (generators execute in their
    caller), which is exactly the thread the ledger accounts.

    ``phases`` (the training loop's) times WHERE the host spends that time:
    ``phases.pull`` around the blocking pull from the source,
    ``phases.put`` around the ``device_put`` of a batch."""
    sharding = mesh_lib.batch_sharding(mesh)

    def put(item):
        return jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), sharding) if a is not None else None,
            item, is_leaf=lambda a: a is None or not isinstance(a, (list, tuple, dict)))

    def note(category):
        if ledger is not None:
            ledger.note(category)

    pulling = phases.pull if phases is not None else contextlib.nullcontext()
    putting = phases.put if phases is not None else contextlib.nullcontext()
    src = _ThreadedIterator(it, buffer_size=buffer_size + 2) if threaded else it
    src = iter(src)
    buf: collections.deque = collections.deque()
    note("idle")                    # body first runs at the first next()
    try:
        while True:
            with pulling:
                item = next(src, _EXHAUSTED)
            if item is _EXHAUSTED:
                break
            with putting:
                buf.append(put(item))
            if len(buf) > buffer_size:
                note("data_wait")
                yield buf.popleft()
                note("device_step")
        while buf:
            note("data_wait")
            yield buf.popleft()
            note("device_step")
    finally:
        note("data_wait")           # close the pipeline's own tail
        if threaded:
            src.close()


# ---------------------------------------------------------------------------
# disk tier (DiskFeatureSet, FeatureSet.scala:332-409)
# ---------------------------------------------------------------------------

class DiskFeatureSet(FeatureSet):
    """``DISK_AND_DRAM(numSlice)`` semantics (``FeatureSet.scala:332-409``):
    the dataset lives on disk (standard ``.npy`` files, memory-mapped by the
    native IO library); each training pass materializes a random
    ``1/num_slices`` DRAM slice, and the NEXT pass's pages stream in on a
    background thread while the current slice trains. ``EveryEpoch``-style
    triggers and ``nb_epoch`` count FULL passes (``num_slices`` slice
    passes), matching ``ZooTrigger.scala:44-66``.

    ``num_slices == 0`` declares an evaluation-only set (whole set readable,
    no training slices), mirroring the reference's contract.
    """

    def __init__(self, x_paths, y_path: Optional[str] = None,
                 num_slices: int = 2, shuffle: bool = True, seed: int = 0):
        from ..native import NativeArrayFile
        if num_slices == 1 or num_slices < 0:
            raise ValueError(
                "num_slices must be 0 (eval-only) or >= 2; for everything "
                "in DRAM use FeatureSet.array (the reference's DRAM type)")
        paths = [x_paths] if isinstance(x_paths, (str, bytes)) else list(x_paths)
        self.files_x = [NativeArrayFile(p) for p in paths]
        self.file_y = NativeArrayFile(y_path) if y_path is not None else None
        self.total = self.files_x[0].n
        for f in self.files_x:
            if f.n != self.total:
                raise ValueError("feature files disagree on record count")
        if self.file_y is not None and self.file_y.n != self.total:
            raise ValueError("label file disagrees with features on count")
        self.num_slices = int(num_slices)
        self.shuffle = shuffle
        self.seed = seed
        self.slice_size = (self.total // self.num_slices
                           if self.num_slices else self.total)
        self._cur: Optional[Tuple[int, List[np.ndarray], Any]] = None

    # -- factory ------------------------------------------------------------
    @staticmethod
    def disk(x_paths, y_path=None, *, num_slices: int = 2,
             shuffle: bool = True, seed: int = 0) -> "DiskFeatureSet":
        return DiskFeatureSet(x_paths, y_path, num_slices, shuffle, seed)

    # -- protocol -----------------------------------------------------------
    @property
    def num_of_slice(self) -> int:
        return self.num_slices

    def __len__(self) -> int:
        return self.slice_size

    def _slice_indices(self, pass_idx: int) -> np.ndarray:
        """Record indices of slice ``pass_idx``, SORTED for sequential disk
        reads (within-slice order is reshuffled by ``_order`` anyway)."""
        if self.shuffle:
            rng = np.random.default_rng(self.seed + 7919 * pass_idx)
            idx = rng.choice(self.total, size=self.slice_size, replace=False)
            idx.sort()
            return idx
        # modular rotation so a total that doesn't divide num_slices still
        # covers every record across passes (no permanently-dropped tail)
        lo = (pass_idx * self.slice_size) % self.total
        return (np.arange(lo, lo + self.slice_size) % self.total)

    def _materialize(self, pass_idx: int) -> None:
        if self._cur is not None and self._cur[0] == pass_idx:
            return
        idx = self._slice_indices(pass_idx)
        xs = [f.gather(idx) for f in self.files_x]
        y = self.file_y.gather(idx) if self.file_y is not None else None
        self._cur = (pass_idx, xs, y)
        # stream the NEXT slice's pages in while this one trains — only in
        # rotation mode, where the next slice is a dense range; a shuffled
        # slice's sorted sample spans ~the whole file, and prefetching all
        # of it would read num_slices× the IO the slicing exists to avoid
        if not self.shuffle:
            nxt = self._slice_indices(pass_idx + 1)
            lo, hi = int(nxt.min()), int(nxt.max()) + 1
            for f in self.files_x + ([self.file_y] if self.file_y else []):
                f.prefetch(lo, hi)

    def iter_batches(self, batch_size: int, *, epoch: int = 0,
                     drop_last: bool = True):
        if self.num_slices == 0:
            raise ValueError("num_slices=0 is an evaluation-only "
                             "DiskFeatureSet — it cannot train "
                             "(FeatureSet.scala:369-375)")
        self._materialize(epoch)
        _, xs, y = self._cur
        order = self._order(epoch)
        n = self.slice_size
        end = n - (n % batch_size) if drop_last else n
        for i in range(0, end, batch_size):
            sel = order[i:i + batch_size]
            bx = [a[sel] for a in xs]
            yield (bx if len(bx) > 1 else bx[0],
                   None if y is None else y[sel])

    def sample(self, n: int):
        """First ``n`` records straight from disk — no full-set gather."""
        idx = np.arange(min(n, self.total))
        bx = [f.gather(idx) for f in self.files_x]
        return bx if len(bx) > 1 else bx[0]

    # whole-set views (the reference's data(train=false) path)
    @property
    def xs(self):  # type: ignore[override]
        all_idx = np.arange(self.total)
        return [f.gather(all_idx) for f in self.files_x]

    @property
    def x(self):
        xs = self.xs
        return xs if len(xs) > 1 else xs[0]

    @property
    def y(self):
        if self.file_y is None:
            return None
        return self.file_y.gather(np.arange(self.total))

    def close(self):
        for f in self.files_x + ([self.file_y] if self.file_y else []):
            f.close()


class BucketedFeatureSet(FeatureSet):
    """Length-bucketed dataset for ragged sequences under XLA static
    shapes (SURVEY §7 "hard parts": the reference just pads everything to
    one length — bucketing compiles one program per bucket and wastes far
    less padding compute). Batches never mix buckets; batch order
    interleaves buckets, reshuffled per epoch.
    """

    ragged = True             # evaluate/predict need a single dense array

    def __init__(self, buckets: Sequence[FeatureSet], shuffle: bool = True,
                 seed: int = 0):
        buckets = [b for b in buckets if len(b) > 0]
        if not buckets:
            raise ValueError("BucketedFeatureSet needs non-empty buckets")
        self.buckets = list(buckets)
        self.shuffle = shuffle
        self.seed = seed

    def __len__(self) -> int:
        return sum(len(b) for b in self.buckets)

    def steps_per_epoch(self, batch_size: int, drop_last: bool = True) -> int:
        return sum(b.steps_per_epoch(batch_size, drop_last)
                   for b in self.buckets)

    def iter_batches(self, batch_size: int, *, epoch: int = 0,
                     drop_last: bool = True):
        iters = [b.iter_batches(batch_size, epoch=epoch, drop_last=drop_last)
                 for b in self.buckets]
        order = [bi for bi, b in enumerate(self.buckets)
                 for _ in range(b.steps_per_epoch(batch_size, drop_last))]
        if self.shuffle:
            np.random.default_rng(self.seed + 31 * epoch).shuffle(order)
        for bi in order:
            yield next(iters[bi])

    def sample(self, n: int):
        return self.buckets[0].sample(n)

    @property
    def xs(self):  # type: ignore[override]
        raise ValueError("bucketed data is ragged across buckets; iterate "
                         "with iter_batches or use the per-bucket sets")

    @property
    def x(self):
        return self.xs

    @property
    def y(self):
        ys = [b.y for b in self.buckets]
        if any(v is None for v in ys):
            return None
        return np.concatenate(ys)
