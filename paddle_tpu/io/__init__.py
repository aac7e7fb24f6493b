"""Data loading.

Reference parity: python/paddle/io/ — Dataset/IterableDataset/TensorDataset
(dataset.py), BatchSampler/DistributedBatchSampler (batch_sampler.py),
DataLoader with multiprocess workers (reader.py:216, dataloader_iter.py).
TPU-native: workers feed host numpy batches; device transfer is a single
jnp.asarray per batch (XLA owns the H2D pipeline); prefetching via a
background thread pool instead of shared-memory queues.
"""
from __future__ import annotations

import itertools
import math
import queue
import threading
import time as _time
from typing import Iterable, List, Optional

import numpy as np

from ..core.tensor import Tensor
from ..framework import dtype as dtype_mod


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        n = len(tensors[0])
        assert all(len(t) == n for t in tensors)
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return len(self.tensors[0])


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, tuple) else (item,))
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for d in self.datasets:
            yield from d


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets])

    def __len__(self):
        return int(self.cum[-1])

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        ds = int(np.searchsorted(self.cum, idx, side="right"))
        prev = 0 if ds == 0 else int(self.cum[ds - 1])
        return self.datasets[ds][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    if all(isinstance(l, float) for l in lengths):
        total = len(dataset)
        lengths = [int(math.floor(total * l)) for l in lengths]
        lengths[-1] = total - sum(lengths[:-1])
    idx = np.random.permutation(len(dataset))
    out, off = [], 0
    for l in lengths:
        out.append(Subset(dataset, idx[off : off + l].tolist()))
        off += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None, generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self.num_samples = num_samples or len(data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class SubsetRandomSampler(Sampler):
    """Sample WITHOUT replacement from a fixed index subset
    (reference io/dataloader/sampler.py SubsetRandomSampler)."""

    def __init__(self, indices):
        self.indices = list(indices)

    def __iter__(self):
        return iter([self.indices[i] for i in np.random.permutation(len(self.indices))])

    def __len__(self):
        return len(self.indices)


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        return iter(np.random.choice(len(p), self.num_samples, replace=self.replacement, p=p).tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    """python/paddle/io/dataloader/batch_sampler.py parity."""

    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Per-rank sharded batches (dataloader/batch_sampler.py
    DistributedBatchSampler): pads to equal length, epoch-seeded shuffle."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None, shuffle=False, drop_last=False):
        if num_replicas is None or rank is None:
            from ..distributed import get_rank, get_world_size

            num_replicas = num_replicas if num_replicas is not None else get_world_size()
            rank = rank if rank is not None else get_rank()
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas
        self.local_rank = rank
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: self.total_size - n]
        local = indices[self.local_rank : self.total_size : self.nranks]
        batch = []
        for idx in local:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


def _collate(batch, wrap):
    """One recursive collate (python/paddle/io/dataloader/collate.py parity):
    `wrap` turns a stacked numpy leaf into the output leaf type — Tensor for
    the in-process path, identity for worker processes (which must never
    touch jax)."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return wrap(_np_stack([np.asarray(s.numpy()) for s in batch]))
    if isinstance(sample, np.ndarray):
        return wrap(_np_stack(list(batch)))
    if isinstance(sample, (int, np.integer)):
        return wrap(np.asarray(batch, np.int64))
    if isinstance(sample, (float, np.floating)):
        return wrap(np.asarray(batch, np.float32))
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: _collate([b[k] for b in batch], wrap) for k in sample}
    if isinstance(sample, (tuple, list)):
        return [_collate(list(items), wrap) for items in zip(*batch)]
    return list(batch)


def default_collate_fn(batch):
    """python/paddle/io/dataloader/collate.py parity: stack leaves."""
    return _collate(batch, Tensor)


def _collate_np(batch):
    """default collate with numpy leaves — used INSIDE worker processes,
    which must never touch jax; the parent re-wraps leaves as Tensors
    (_np_to_tensor)."""
    return _collate(batch, lambda a: a)


def _np_stack(arrays):
    a = np.stack(arrays)
    return a.astype(np.float32) if a.dtype == np.float64 else a


def _tensor_leaves_to_np(obj):
    """Pre-pickle scrub for worker-process payloads: Tensors -> numpy."""
    if isinstance(obj, Tensor):
        return np.asarray(obj.numpy())
    if isinstance(obj, dict):
        return {k: _tensor_leaves_to_np(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_tensor_leaves_to_np(v) for v in obj]
    if isinstance(obj, tuple):
        return tuple(_tensor_leaves_to_np(v) for v in obj)
    return obj


def _np_to_tensor(obj):
    if isinstance(obj, np.ndarray) and not obj.dtype.hasobject:
        return Tensor(obj)
    if isinstance(obj, dict):
        return {k: _np_to_tensor(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_np_to_tensor(v) for v in obj]
    if isinstance(obj, tuple):
        return tuple(_np_to_tensor(v) for v in obj)
    return obj


class _PrefetchIter:
    """Background-thread prefetch (the TPU-side replacement for the
    reference's multiprocess shared-memory workers in dataloader_iter.py:
    batch assembly is numpy-light; overlap host collate with device step)."""

    def __init__(self, gen_fn, depth):
        self._q = queue.Queue(maxsize=depth)
        self._done = object()
        self._exc = None

        def worker():
            try:
                for item in gen_fn():
                    self._q.put(item)
            except BaseException as e:  # propagate to consumer
                self._exc = e
            finally:
                self._q.put(self._done)

        self._t = threading.Thread(target=worker, daemon=True)
        self._t.start()

    def __iter__(self):
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._done:
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        return item


class _NativeRingIter:
    """Prefetch through the native fixed-buffer ring (paddle_tpu/native):
    the producer thread serializes host (numpy) batches into reusable C++
    buffers with a multi-threaded memcpy (GIL released), playing the role of
    the reference's shared-memory worker queues
    (python/paddle/io/dataloader/dataloader_iter.py). Protocol: every batch
    puts one record on a Python side queue — ("ring", spec) if its payload
    went through the ring, ("py", batch) for anything else (device Tensors,
    nested structures, oversized batches) — so the consumer pops the side
    queue first and only then the ring, preserving order. The ring is
    created lazily on the first numpy batch, sized to it; batch types come
    out exactly as the non-ring paths produce them."""

    _RING_BYTES_MAX = 64 << 20

    def __init__(self, gen_fn, depth):
        from ..native.ring import PrefetchRing  # raises NativeUnavailable early

        from ..native import get_lib

        get_lib()  # fail fast (caught by DataLoader.__iter__) if no native core
        self._PrefetchRing = PrefetchRing
        self._depth = max(2, min(depth, 16))
        self._ring = None
        self._side = queue.Queue(maxsize=max(depth * 2, 4))
        self._exc = None
        self._done = False
        self._eof = object()

        def to_leaves(batch):
            # ring carries host bytes; device Tensors ride the side channel
            # unchanged (no D2H bounce), as do nested/non-array structures
            if isinstance(batch, np.ndarray) and not batch.dtype.hasobject:
                return None, [batch]
            if (
                isinstance(batch, (tuple, list))
                and batch
                and all(isinstance(x, np.ndarray) and not x.dtype.hasobject for x in batch)
            ):
                return len(batch), list(batch)
            raise TypeError

        def producer():
            try:
                for batch in gen_fn():
                    rec = None
                    try:
                        spec, leaves = to_leaves(batch)
                        if self._ring is None:
                            nbytes = sum(a.nbytes for a in leaves)
                            cap = min(self._RING_BYTES_MAX, max(1 << 20, 2 * nbytes))
                            self._ring = self._PrefetchRing(capacity=self._depth, buffer_bytes=cap)
                        if not self._ring.put_arrays(leaves):
                            return  # consumer tore down the ring
                        rec = ("ring", spec)
                    except (TypeError, ValueError):
                        rec = ("py", batch)
                    self._side.put(rec)
            except BaseException as e:  # propagate dataset/collate errors
                self._exc = e
            finally:
                if self._ring is not None:
                    self._ring.close()
                self._side.put(self._eof)

        self._t = threading.Thread(target=producer, daemon=True)
        self._t.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        rec = self._side.get()
        if rec is self._eof:
            self._shutdown()
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        kind, payload = rec
        if kind == "py":
            return payload
        arrays = self._ring.get_arrays()
        if arrays is None:  # ring closed underneath us (shutdown race)
            self._shutdown()
            raise StopIteration
        if payload is None:  # single-array batch
            return arrays[0]
        return list(arrays)

    def _shutdown(self):
        self._done = True
        if self._ring is not None:
            self._ring.close()  # unblocks a producer stuck in acquire_fill
        deadline = _time.monotonic() + 10
        while self._t.is_alive() and _time.monotonic() < deadline:
            try:  # drain so a producer blocked on the bounded side queue exits
                self._side.get_nowait()
            except queue.Empty:
                self._t.join(timeout=0.05)
        if self._ring is not None and not self._t.is_alive():
            self._ring.destroy()
            self._ring = None

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass


def _mp_worker_main(task_q, out_q, dataset, collate_fn, use_np_default, worker_init_fn, w):
    """Spawned persistent worker entry (module-level: must pickle). Serves
    epoch after epoch of batch-index tasks; ships numpy payloads; never
    touches jax device state. Custom collate_fns run here too and must stay
    numpy-only — building device Tensors in a worker would initialize a
    second accelerator client per process (documented DataLoader contract)."""
    import pickle

    import jax

    # the chip belongs to the parent: whatever dataset/collate code does in
    # here, this process may only ever see the host platform (importing the
    # framework initialises no backend, so the pin lands first)
    jax.config.update("jax_platforms", "cpu")
    try:
        if worker_init_fn is not None:
            worker_init_fn(w)
        collate = _collate_np if use_np_default else collate_fn
        while True:
            task = task_q.get()
            if task is None:
                return
            for idxs in task:
                out = collate([dataset[i] for i in idxs])
                out_q.put(("ok", _tensor_leaves_to_np(out)))
            out_q.put(("eof", None))
    except BaseException as e:
        # mp.Queue pickles in a FEEDER THREAD — put() of an unpicklable
        # exception "succeeds" here and then dies silently over there,
        # leaving the parent waiting forever. Probe first.
        try:
            pickle.dumps(e)
        except Exception:
            e = RuntimeError(f"{type(e).__name__}: {e}")
        out_q.put(("err", e))


class _MPWorkerPool:
    """Persistent multiprocess workers for map-style datasets: batch b of an
    epoch is built by worker b % num_workers in its own process (the
    reference's dataloader_iter.py worker design + persistent_workers
    semantics). Order is restored by round-robin consumption, one result
    queue per worker so a slow worker backpressures only itself.

    Workers are SPAWNED once per DataLoader and reused across epochs: the
    parent runs the accelerator client's threads, and forking a
    multithreaded jax process deadlocks (observed on batches >~10 MB), so
    fork is out; spawn pays a child interpreter + import cost, which
    persistence amortizes to once per loader instead of once per epoch."""

    def __init__(self, dataset, collate_fn, num_workers, prefetch, worker_init_fn=None, timeout=0):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        self._nw = num_workers
        self._timeout = timeout  # DataLoader(timeout=...): 0 = no limit
        self._task_qs = [ctx.Queue() for _ in range(num_workers)]
        self._out_qs = [ctx.Queue(maxsize=max(prefetch, 2)) for _ in range(num_workers)]
        use_np_default = collate_fn is default_collate_fn
        self._procs = [
            ctx.Process(
                target=_mp_worker_main,
                args=(self._task_qs[w], self._out_qs[w], dataset,
                      None if use_np_default else collate_fn, use_np_default,
                      worker_init_fn, w),
                daemon=True,
            )
            for w in range(num_workers)
        ]
        for p in self._procs:
            p.start()
        self._alive = True
        self._current = None  # the epoch iterator being served

    def run_epoch(self, batch_indices):
        if self._current is not None and not self._current._clean:
            # the previous epoch's iterator was abandoned mid-way: its
            # unread batches/eof markers are still in the out queues and
            # would leak into this epoch — only safe recovery is a respawn
            self.shutdown()
            raise _PoolAbandoned
        batches = list(batch_indices)
        for w in range(self._nw):
            self._task_qs[w].put(batches[w::self._nw])
        self._current = _MPEpochIter(self, len(batches))
        return self._current

    def _get(self, w):
        """out_qs[w].get with liveness watching: a worker OOM-killed or
        segfaulted in native code never enqueues anything — without this the
        training loop hangs forever (the reference's watchdog pattern)."""
        deadline = (_time.monotonic() + self._timeout) if self._timeout else None
        while True:
            try:
                return self._out_qs[w].get(timeout=2.0)
            except queue.Empty:
                if not self._procs[w].is_alive():
                    code = self._procs[w].exitcode
                    self.shutdown()
                    raise RuntimeError(
                        f"DataLoader worker {w} died unexpectedly (exit code "
                        f"{code}) — killed by the OS (OOM?) or crashed in "
                        "native code"
                    )
                if deadline is not None and _time.monotonic() > deadline:
                    self.shutdown()
                    raise RuntimeError(
                        f"DataLoader worker {w} timed out after {self._timeout}s"
                    )

    def shutdown(self):
        if not self._alive:
            return
        self._alive = False
        for q in self._task_qs:
            try:
                q.put(None)
            except Exception:
                pass
        for p in self._procs:
            p.join(timeout=2)
        for p in self._procs:
            if p.is_alive():
                p.terminate()
                p.join(timeout=5)
        for q in self._task_qs + self._out_qs:
            q.close()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass


class _PoolAbandonedType(Exception):
    pass


_PoolAbandoned = _PoolAbandonedType()


class _MPEpochIter:
    def __init__(self, pool, n_batches):
        self._pool = pool
        self._n = n_batches
        self._next = 0
        self._clean = False  # fully consumed + eofs drained

    def __iter__(self):
        return self

    def __next__(self):
        if self._next >= self._n:
            if not self._clean:
                # pop each worker's trailing eof so its queue is clean for
                # the next epoch
                for w in range(self._pool._nw):
                    kind, payload = self._pool._get(w)
                    if kind == "err":
                        self._pool.shutdown()
                        raise payload
                self._clean = True
            raise StopIteration
        kind, payload = self._pool._get(self._next % self._pool._nw)
        if kind == "err":
            self._pool.shutdown()
            raise payload
        self._next += 1
        return _np_to_tensor(payload)


class DataLoader:
    """python/paddle/io/reader.py:216 parity.

    Worker modes: num_workers=0 is synchronous; num_workers>0 uses the
    thread + native prefetch ring by default; persistent_workers=True
    spawns persistent worker PROCESSES (map-style datasets only — needs a
    picklable dataset/collate_fn/worker_init_fn). If spawn fails (e.g.
    unpicklable local classes), loading falls back to the thread path with
    a UserWarning — and `worker_init_fn` does NOT run on that fallback
    (threads share the parent's state; per-worker init has no process to
    initialize)."""

    def __init__(
        self,
        dataset,
        feed_list=None,
        places=None,
        return_list=True,
        batch_sampler=None,
        batch_size=1,
        shuffle=False,
        drop_last=False,
        collate_fn=None,
        num_workers=0,
        use_buffer_reader=True,
        prefetch_factor=2,
        use_shared_memory=True,
        timeout=0,
        worker_init_fn=None,
        persistent_workers=False,
    ):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self._worker_init_fn = worker_init_fn
        self._persistent = bool(persistent_workers)
        self._timeout = timeout or 0
        self.use_shared_memory = use_shared_memory  # native fixed-buffer ring
        self.prefetch = max(prefetch_factor, 1) if use_buffer_reader else 0
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last)

    def _gen(self):
        if self._iterable_mode:
            batch = []
            for item in self.dataset:
                batch.append(item)
                if self.batch_size is not None and len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last:
                yield self.collate_fn(batch)
            return
        for batch_idx in self.batch_sampler:
            yield self.collate_fn([self.dataset[i] for i in batch_idx])

    def _depth(self):
        depth = self.prefetch * max(self.num_workers, 1)
        try:  # incubate.autotune dataloader tuning: deepen prefetch
            from ..incubate.autotune import get_config
        except ImportError:
            get_config = None
        if get_config is not None and get_config()["dataloader"].get("enable"):
            depth = max(2 * depth, 8)
        return depth

    def _prefetch_iter(self):
        """Thread (+ native ring) prefetch: one producer thread."""
        depth = self._depth()
        if self.use_shared_memory:
            from ..native import NativeUnavailable

            try:
                return _NativeRingIter(self._gen, depth)
            except (NativeUnavailable, MemoryError):
                pass  # no native core / no memory: python-queue prefetch
        return _PrefetchIter(self._gen, depth)

    def _mp_iter(self):
        pool = getattr(self, "_mp_pool", None)
        if pool is None or not pool._alive:
            self._mp_pool = _MPWorkerPool(
                self.dataset, self.collate_fn, self.num_workers,
                self._depth(), self._worker_init_fn, self._timeout,
            )
        try:
            return self._mp_pool.run_epoch(list(self.batch_sampler))
        except _PoolAbandonedType:
            # previous epoch iterator abandoned mid-way: queues are dirty,
            # pool was shut down — respawn once, clean
            self._mp_pool = _MPWorkerPool(
                self.dataset, self.collate_fn, self.num_workers,
                self._depth(), self._worker_init_fn, self._timeout,
            )
            return self._mp_pool.run_epoch(list(self.batch_sampler))

    def _record_worker_fallback(self, exc) -> None:
        """Process->thread degradation accounting: warn once per loader with
        the reason, count every occurrence
        (`paddle_tpu_dataloader_fallbacks_total{reason}`)."""
        reason = type(exc).__name__
        try:
            from .. import telemetry as _tm

            if _tm.enabled():
                _tm.counter(
                    "paddle_tpu_dataloader_fallbacks_total",
                    "DataLoader worker-process spawns degraded to thread "
                    "prefetch (unpicklable dataset/collate, no mp, ...)",
                    ("reason",),
                ).labels(reason=reason).inc()
        except Exception:
            pass  # accounting must never break data loading
        if getattr(self, "_fallback_warned", False):
            return
        self._fallback_warned = True
        import warnings

        warnings.warn(
            f"DataLoader(persistent_workers=True): worker spawn failed "
            f"({reason}: {exc}); falling back to thread prefetch "
            "(worker_init_fn will NOT run)",
            stacklevel=3,
        )

    def __del__(self):
        pool = getattr(self, "_mp_pool", None)
        if pool is not None:
            try:
                pool.shutdown()
            except Exception:
                pass

    def __iter__(self):
        if self.prefetch and self.num_workers != 0:
            # persistent_workers -> real worker PROCESSES (the reference's
            # dataloader_iter.py + persistent_workers semantics): wins on
            # GIL-bound Python/PIL pipelines (benchmarks/dataloader_bench.py
            # — 1.34x even on this 1-core container, ~Ncores on real hosts),
            # at a one-time spawned-interpreter cost amortized over epochs.
            # Default stays thread+native-ring: zero startup tax, right for
            # numpy-light collate. Iterable datasets always thread (no index
            # sharding without worker_info).
            if self.num_workers > 0 and self._persistent and not self._iterable_mode:
                try:
                    return self._mp_iter()
                except (TypeError, AttributeError, OSError, ImportError) as e:
                    # spawn needs a picklable dataset/collate/worker_init_fn;
                    # degrade loudly, not silently — the user asked for
                    # worker processes and is getting a thread. The warning
                    # fires ONCE per loader (every epoch re-enters here and
                    # a 100-epoch run must not emit 100 identical lines);
                    # the fallback COUNTER increments every time so
                    # dashboards still see the real rate.
                    self._record_worker_fallback(e)
            return self._prefetch_iter()
        return self._gen()

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)


def get_worker_info():
    return None


# the streaming data tier (sharded/resumable/device-prefetched input —
# ROADMAP item 4) lives in its own subpackage; imported last because its
# loader builds on the Dataset/collate/prefetch machinery above
from . import streaming  # noqa: E402,F401
