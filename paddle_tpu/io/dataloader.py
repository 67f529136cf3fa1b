"""DataLoader (reference: ``python/paddle/io/dataloader/dataloader_iter.py``
+ ``worker.py`` — multiprocess workers + pinned-memory + prefetch).

TPU-native host loop: workers produce numpy batches, a bounded prefetch queue
overlaps host data prep with device steps (the jitted step's async dispatch
means the host runs ahead; the queue keeps it fed).

``num_workers>0`` defaults to a thread pool (numpy collate releases the
GIL, so threads are usually the right TPU-host choice — and they need no
dataset pickling or __main__ guard). ``worker_mode="process"`` opts into
real OS worker processes (spawn context — fork is unsafe after jax backend
init) with an order-preserving reorder buffer and worker-crash propagation,
like the reference's _DataLoaderIterMultiProcess.
"""
from __future__ import annotations

import queue
import threading
import traceback
from typing import Callable, Optional

import numpy as np

from ..core.tensor import Tensor
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler


class _WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


def _mp_worker_loop(dataset, index_queue, result_queue, collate_fn, wid,
                    num_workers, worker_init_fn, ring_name=None):
    """Worker-process main (reference ``worker.py::_worker_loop``): pull
    (task_id, indices), fetch+collate, push (task_id, batch, error).

    With ``ring_name``, results travel through the native shared-memory
    ring (paddle_tpu.csrc.ShmRing — one memcpy into the mmap'd segment)
    instead of being pickled through the mp.Queue pipe."""
    import pickle

    _worker_info.info = _WorkerInfo(wid, num_workers, dataset)
    ring = None
    if ring_name is not None:
        try:
            from ..csrc import ShmRing
            ring = ShmRing.open(ring_name)
        except Exception:
            ring = None  # fall back to the queue

    def emit(rec):
        if ring is not None:
            try:
                ring.push(pickle.dumps(rec,
                                       protocol=pickle.HIGHEST_PROTOCOL))
                return
            except ValueError:
                pass  # record larger than the ring: use the queue
        result_queue.put(rec)

    try:
        if worker_init_fn is not None:
            worker_init_fn(wid)
        while True:
            task = index_queue.get()
            if task is None:
                break
            task_id, indices = task
            try:
                batch = collate_fn([dataset[i] for i in indices])
                emit((task_id, batch, None))
            except Exception as e:  # noqa: BLE001 — propagated to parent
                emit((task_id, None,
                      f"{type(e).__name__}: {e}\n{traceback.format_exc()}"))
    except KeyboardInterrupt:
        pass
    finally:
        if ring is not None:
            ring.mark_closed()
            ring.close(unlink=False)


def default_collate_fn(batch):
    """Stack a list of samples into batched numpy arrays (paddle semantics)."""
    sample = batch[0]
    if isinstance(sample, (np.ndarray, np.generic)):
        return np.stack(batch)
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s.value) for s in batch])
    if isinstance(sample, (int, float)):
        return np.asarray(batch)
    if isinstance(sample, (str, bytes)):
        return batch
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch]) for k in sample}
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn(list(items))
                            for items in zip(*batch))
    return batch


def _to_tensor_batch(batch, return_list=True):
    if isinstance(batch, np.ndarray):
        return Tensor(batch)
    if isinstance(batch, dict):
        return {k: _to_tensor_batch(v) for k, v in batch.items()}
    if isinstance(batch, (list, tuple)):
        return [_to_tensor_batch(b) for b in batch]
    return batch


class DataLoader:
    def __init__(self, dataset: Dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn: Optional[Callable] = None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, worker_mode="thread"):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = int(num_workers)
        self.prefetch_factor = max(2, int(prefetch_factor))
        self.worker_init_fn = worker_init_fn
        self.timeout = float(timeout)
        self.use_shared_memory = bool(use_shared_memory)
        if worker_mode not in ("thread", "process"):
            raise ValueError(f"worker_mode must be 'thread' or 'process', "
                             f"got {worker_mode!r}")
        self.worker_mode = worker_mode
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            if batch_size is None:
                self.batch_sampler = None
                self.batch_size = None
            else:
                self.batch_sampler = BatchSampler(
                    dataset, shuffle=shuffle, batch_size=batch_size,
                    drop_last=drop_last)

    def __len__(self):
        if self._iterable:
            raise TypeError("IterableDataset has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    # ------------------------------------------------------------------ iter
    def __iter__(self):
        if self._iterable:
            yield from self._iter_iterable()
        elif self.num_workers == 0:
            yield from self._iter_sync()
        elif self.worker_mode == "process":
            yield from self._iter_multiprocess()
        else:
            yield from self._iter_prefetch()

    def _fetch(self, indices):
        samples = [self.dataset[i] for i in indices]
        return self.collate_fn(samples)

    def _iter_sync(self):
        if self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield _to_tensor_batch(self.collate_fn([self.dataset[i]]))
            return
        for indices in self.batch_sampler:
            yield _to_tensor_batch(self._fetch(indices))

    def _iter_iterable(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if self.batch_size and len(batch) == self.batch_size:
                yield _to_tensor_batch(self.collate_fn(batch))
                batch = []
        if batch and not self.drop_last:
            yield _to_tensor_batch(self.collate_fn(batch))

    def _iter_prefetch(self):
        """Thread-pool prefetch: num_workers fetchers, bounded output queue,
        order-preserving (matches reference's _DataLoaderIterMultiProcess
        reorder buffer)."""
        from concurrent.futures import ThreadPoolExecutor

        depth = self.num_workers * self.prefetch_factor
        batches = list(self.batch_sampler)
        with ThreadPoolExecutor(max_workers=self.num_workers,
                                thread_name_prefix="dataloader") as pool:
            if self.worker_init_fn:
                for wid in range(self.num_workers):
                    pool.submit(self.worker_init_fn, wid)
            futures = queue.Queue()
            it = iter(batches)

            def submit_next():
                try:
                    indices = next(it)
                except StopIteration:
                    return False
                futures.put(pool.submit(self._fetch, indices))
                return True

            for _ in range(min(depth, len(batches))):
                submit_next()
            while not futures.empty():
                fut = futures.get()
                submit_next()
                yield _to_tensor_batch(fut.result())

    def _iter_multiprocess(self):
        """Spawn-context worker processes + order-preserving reorder buffer
        + crash propagation (reference _DataLoaderIterMultiProcess)."""
        import multiprocessing as mp

        import os

        ctx = mp.get_context("spawn")
        index_q = ctx.Queue()
        result_q = ctx.Queue()
        nw = self.num_workers
        # native shared-memory result transport (one ring per worker) when
        # use_shared_memory and the csrc module builds; else the mp.Queue
        rings = []
        ring_names = [None] * nw
        if self.use_shared_memory:
            try:
                from ..csrc import ShmRing, available
                if available():
                    import uuid
                    tag = uuid.uuid4().hex[:12]
                    for wid in range(nw):
                        name = f"/pt_dl_{os.getpid()}_{tag}_{wid}"
                        rings.append(ShmRing.create(name, 1 << 23))
                        ring_names[wid] = name
            except Exception:
                for r in rings:
                    r.close(unlink=True)
                rings, ring_names = [], [None] * nw
        workers = [
            ctx.Process(
                target=_mp_worker_loop,
                args=(self.dataset, index_q, result_q, self.collate_fn, wid,
                      nw, self.worker_init_fn, ring_names[wid]),
                daemon=True)
            for wid in range(nw)]
        # workers are host-side data producers: pin them to the CPU jax
        # platform so their paddle_tpu import never initializes (or
        # blocks on) the accelerator backend the trainer process owns —
        # a chip belongs to one process at a time, and the trainer IS
        # that process while the loader runs
        overrides = {"JAX_PLATFORMS": "cpu"}
        saved = {k: os.environ.get(k) for k in overrides}
        os.environ.update(overrides)
        try:
            for w in workers:
                w.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        batches = list(self.batch_sampler)
        depth = min(nw * self.prefetch_factor, len(batches))
        poll_s = self.timeout if self.timeout > 0 else 5.0

        def result_get():
            """One (tid, batch, err) record; raises queue.Empty after
            poll_s. With rings active the queue is polled too — a worker
            falls back per-record when its ring can't take a message (open
            failure, oversized batch)."""
            if not rings:
                return result_q.get(timeout=poll_s)
            import pickle
            import time as time_mod
            deadline = time_mod.monotonic() + poll_s
            while True:
                for r in rings:
                    try:
                        data = r.pop(timeout_ms=20)
                    except EOFError:
                        continue  # that worker finished and hung up
                    if data is not None:
                        return pickle.loads(data)
                try:
                    return result_q.get_nowait()
                except queue.Empty:
                    pass
                if time_mod.monotonic() > deadline:
                    raise queue.Empty

        try:
            for i in range(depth):
                index_q.put((i, batches[i]))
            next_submit = depth
            next_out = 0
            buffer = {}
            while next_out < len(batches):
                if next_out in buffer:
                    batch = buffer.pop(next_out)
                    next_out += 1
                    if next_submit < len(batches):
                        index_q.put((next_submit, batches[next_submit]))
                        next_submit += 1
                    yield _to_tensor_batch(batch)
                    continue
                try:
                    tid, batch, err = result_get()
                except queue.Empty:
                    dead = [w.pid for w in workers if not w.is_alive()]
                    if dead:
                        raise RuntimeError(
                            f"DataLoader worker(s) {dead} exited "
                            f"unexpectedly") from None
                    if self.timeout > 0:
                        raise RuntimeError(
                            f"DataLoader timed out after {self.timeout}s "
                            f"waiting for a worker batch") from None
                    continue
                if err is not None:
                    raise RuntimeError(
                        f"DataLoader worker raised:\n{err}")
                buffer[tid] = batch
        finally:
            for _ in workers:
                try:
                    index_q.put(None)
                except Exception:
                    pass
            for w in workers:
                w.join(timeout=2.0)
                if w.is_alive():
                    w.terminate()
            for r in rings:
                r.close(unlink=True)
