"""Data loading (reference: `python/paddle/io/`).

Host-side pipeline: Dataset/IterableDataset/Sampler/BatchSampler/DataLoader
with multi-threaded prefetch. TPU-first notes:

- ``DistributedBatchSampler`` shards by *process* (host), matching JAX's
  per-host data-parallel input convention — each host loads only its shard
  and `jax.make_array_from_process_local_data`-style feeding assembles the
  global batch (reference: `io/dataloader/batch_sampler.py` DistributedBatchSampler).
- ``num_workers > 0`` uses worker PROCESSES (reference
  `io/dataloader/dataloader_iter.py:358` _DataLoaderIterMultiProcess):
  workers run dataset indexing + collate and ship NUMPY trees back —
  optionally through POSIX shared memory (``use_shared_memory``) for big
  batches — and the parent re-wraps arrays as Tensors. Python-heavy
  transforms therefore scale past the GIL. Threaded mode remains as the
  fallback for unpicklable datasets under a spawn context (fork needs no
  pickling) and is the right choice for GIL-releasing IO/decode loads.
"""

from __future__ import annotations

import itertools
import multiprocessing
import pickle
import queue
import threading
import traceback as _traceback
from typing import Any, Callable, Iterable, Iterator, List, Optional, Sequence, Union

import numpy as np

from ..framework.random import default_generator
from ..tensor.tensor import Tensor

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "ComposeDataset", "ChainDataset",
           "ConcatDataset", "Subset", "random_split", "Sampler", "SequenceSampler",
           "RandomSampler", "WeightedRandomSampler", "BatchSampler",
           "DistributedBatchSampler", "DataLoader", "get_worker_info", "default_collate_fn"]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset is not subscriptable")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors: Sequence):
        self.tensors = [t if isinstance(t, Tensor) else Tensor(np.asarray(t)) for t in tensors]
        n = self.tensors[0].shape[0]
        if any(t.shape[0] != n for t in self.tensors):
            raise ValueError("all tensors must have the same first dimension")

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets: List[Dataset]):
        self.datasets = datasets

    def __getitem__(self, idx):
        out = []
        for ds in self.datasets:
            item = ds[idx]
            out.extend(item if isinstance(item, (list, tuple)) else [item])
        return tuple(out)

    def __len__(self):
        return min(len(ds) for ds in self.datasets)


class ChainDataset(IterableDataset):
    def __init__(self, datasets: List[IterableDataset]):
        self.datasets = datasets

    def __iter__(self):
        for ds in self.datasets:
            yield from ds


class ConcatDataset(Dataset):
    def __init__(self, datasets: List[Dataset]):
        self.datasets = list(datasets)
        self.cumulative_sizes = list(itertools.accumulate(len(d) for d in self.datasets))

    def __len__(self):
        return self.cumulative_sizes[-1]

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        import bisect

        ds_idx = bisect.bisect_right(self.cumulative_sizes, idx)
        prev = 0 if ds_idx == 0 else self.cumulative_sizes[ds_idx - 1]
        return self.datasets[ds_idx][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset: Dataset, indices: Sequence[int]):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset: Dataset, lengths: Sequence, generator=None) -> List[Subset]:
    if all(isinstance(l, float) for l in lengths):
        n = len(dataset)
        counts = [int(np.floor(n * l)) for l in lengths]
        counts[0] += n - sum(counts)
        lengths = counts
    total = sum(lengths)
    if total != len(dataset):
        raise ValueError(f"sum of lengths {total} != dataset size {len(dataset)}")
    rng = _np_rng(generator)
    perm = rng.permutation(total).tolist()
    out, offset = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[offset:offset + l]))
        offset += l
    return out


def _np_rng(generator=None) -> np.random.Generator:
    """numpy RNG seeded from the framework generator: reproducible after
    paddle.seed(), and advancing per draw so epochs differ."""
    gen = generator or default_generator
    if hasattr(gen, "next_key"):
        entropy = np.asarray(gen.next_key()).astype(np.uint32)
        return np.random.default_rng(entropy)
    return np.random.default_rng(gen)


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement: bool = False, num_samples: Optional[int] = None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples
        self.generator = generator

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        rng = _np_rng(self.generator)
        if self.replacement:
            return iter(rng.integers(0, n, self.num_samples).tolist())
        return iter(rng.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples: int, replacement: bool = True):
        super().__init__(None)
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        rng = _np_rng()
        idx = rng.choice(len(self.weights), self.num_samples, replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    """Index batcher with resumable position (health-guard rewind support):
    ``state_dict()``/``set_state_dict()`` capture ``(epoch, position)`` —
    position = batches already yielded this epoch — so a checkpoint can
    pin the data stream and a restart resumes mid-epoch deterministically
    (the index stream must itself be deterministic: seeded shuffle, or the
    epoch-seeded :class:`DistributedBatchSampler`). ``fast_forward(n)``
    additionally skips the next ``n`` batches — how a supervisor-restarted
    run steps past a poisoned data window instead of replaying it.
    Prefetching DataLoader paths materialize the epoch's indices up front
    and re-track position per DELIVERED batch instead (see
    ``DataLoader._track_position``), so snapshots are exact there too."""

    def __init__(self, dataset=None, sampler=None, shuffle: bool = False, batch_size: int = 1,
                 drop_last: bool = False):
        super().__init__(dataset)
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.epoch = 0
        self._position = 0
        self._resume_from = 0

    def set_epoch(self, epoch: int) -> None:
        self.epoch = int(epoch)

    # -- resumable-position protocol ---------------------------------------
    def state_dict(self) -> dict:
        return {"epoch": int(self.epoch), "position": int(self._position)}

    def set_state_dict(self, state: dict) -> None:
        self.set_epoch(state.get("epoch", 0))
        self._resume_from = int(state.get("position", 0))
        self._position = self._resume_from

    def fast_forward(self, n_batches: int) -> None:
        """Skip ``n_batches`` beyond the current/restored position when the
        next epoch iteration starts."""
        self._resume_from = self._position + int(n_batches)
        self._position = self._resume_from

    def _positioned(self, gen):
        """Skip up to the resume point, then track yielded-batch count."""
        start, self._resume_from = self._resume_from, 0
        n = 0
        for batch in gen:
            n += 1
            if n <= start:
                continue
            self._position = n
            yield batch
        self._position = 0  # epoch exhausted; caller owns set_epoch

    def _gen_batches(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __iter__(self):
        return self._positioned(self._gen_batches())

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Per-host sharding (reference: `io/dataloader/batch_sampler.py`
    DistributedBatchSampler): pads to a multiple of num_replicas, subsamples
    rank's slice, optional epoch-seeded shuffle via set_epoch."""

    def __init__(self, dataset, batch_size: int, num_replicas: Optional[int] = None,
                 rank: Optional[int] = None, shuffle: bool = False, drop_last: bool = False):
        self.dataset = dataset
        self.batch_size = batch_size
        if num_replicas is None or rank is None:
            try:
                import jax

                num_replicas = num_replicas if num_replicas is not None else jax.process_count()
                rank = rank if rank is not None else jax.process_index()
            except Exception:
                num_replicas, rank = 1, 0
        self.nranks = num_replicas
        self.local_rank = rank
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self._position = 0
        self._resume_from = 0
        self.num_samples = int(np.ceil(len(dataset) / num_replicas))
        self.total_size = self.num_samples * num_replicas

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def _gen_batches(self):
        # epoch-seeded shuffle: the index stream is a pure function of
        # (epoch, rank), which is what makes the inherited state_dict /
        # fast_forward resume deterministic across a restart
        n = len(self.dataset)
        indices = np.arange(n)
        if self.shuffle:
            rng = np.random.default_rng(self.epoch)
            indices = rng.permutation(n)
        indices = np.concatenate([indices, indices[: self.total_size - n]])
        indices = indices[self.local_rank: self.total_size: self.nranks].tolist()
        batch = []
        for idx in indices:
            batch.append(int(idx))
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __iter__(self):
        return self._positioned(self._gen_batches())

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


class _WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


# -- process-worker transport ------------------------------------------------

_SHM_MIN_BYTES = 1 << 16  # smaller arrays go through the pipe directly


class _ShmArray:
    """Descriptor of an ndarray parked in POSIX shared memory (the
    reference's shared-mem LoDTensor transport, `dataloader_iter.py:150`).
    ``was_tensor`` preserves the batch's python type across the pipe."""

    __slots__ = ("name", "shape", "dtype", "was_tensor")

    def __init__(self, name, shape, dtype, was_tensor=False):
        self.name, self.shape, self.dtype = name, shape, str(dtype)
        self.was_tensor = was_tensor


class _TensorArray:
    """Pipe-path marker: this ndarray was a Tensor on the worker side."""

    __slots__ = ("arr",)

    def __init__(self, arr):
        self.arr = arr


class _WorkerError:
    def __init__(self, exc):
        self.formatted = "".join(_traceback.format_exception(exc))
        self.type_name = type(exc).__name__


class _UnpicklableBatch:
    """Structured worker→parent signal: a custom collate produced a batch
    that cannot cross the mp queue — the parent should rerun the epoch on
    the threaded pool instead of dying mid-iteration."""

    def __init__(self, reason: str):
        self.reason = reason


class _PickledBatch:
    """Custom-collate payload already serialized by the worker (the eager
    validation dump IS the transport — the mp queue then only re-pickles a
    flat bytes object, so nothing is serialized twice)."""

    __slots__ = ("blob",)

    def __init__(self, blob: bytes):
        self.blob = blob


class _PicklingFallback(Exception):
    pass


def _to_transport(obj, use_shm: bool):
    """Worker→parent encoding: Tensors/ndarrays become ndarrays (big ones
    parked in shared memory) with the original type recorded, so the parent
    reconstructs exactly what the sync loader would have yielded."""
    from multiprocessing import resource_tracker, shared_memory

    was_tensor = isinstance(obj, Tensor)
    if was_tensor:
        obj = np.asarray(obj._value)
    if isinstance(obj, np.ndarray):
        if use_shm and obj.nbytes >= _SHM_MIN_BYTES:
            shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
            view = np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)
            np.copyto(view, obj)
            desc = _ShmArray(shm.name, obj.shape, obj.dtype, was_tensor)
            shm.close()
            # the segment is the parent's from here: it attaches, copies
            # and unlinks (_from_transport / _release_transport).  Left
            # registered, this worker's resource tracker unlinks it when
            # the worker exits, which can be before a busy parent has read
            # it (the tracker has no public hand-over; `_name` is what
            # SharedMemory itself registers and unregisters)
            resource_tracker.unregister(shm._name, "shared_memory")
            return desc
        return _TensorArray(obj) if was_tensor else obj
    if isinstance(obj, (list, tuple)):
        return type(obj)(_to_transport(o, use_shm) for o in obj)
    if isinstance(obj, dict):
        return {k: _to_transport(v, use_shm) for k, v in obj.items()}
    return obj


def _release_transport(obj) -> None:
    """Unlink shared-memory segments of a transport payload that will never
    be consumed (early iterator close, worker error)."""
    from multiprocessing import shared_memory

    if isinstance(obj, _ShmArray):
        try:
            shm = shared_memory.SharedMemory(name=obj.name)
            shm.close()
            shm.unlink()
        except FileNotFoundError:
            pass
        return
    if isinstance(obj, (list, tuple)):
        for o in obj:
            _release_transport(o)
    elif isinstance(obj, dict):
        for v in obj.values():
            _release_transport(v)
    # _TensorArray / plain ndarrays hold no shared-memory resources


def _from_transport(obj, tensorify: bool):
    """Parent-side decoding. ``tensorify``: the worker ran the numpy twin of
    the default collate, so every array becomes a Tensor (matching the sync
    path); custom collates keep their own types (ndarray stays ndarray,
    worker-side Tensors come back as Tensors)."""
    from multiprocessing import shared_memory

    if isinstance(obj, _ShmArray):
        shm = shared_memory.SharedMemory(name=obj.name)
        try:
            arr = np.array(np.ndarray(obj.shape, obj.dtype, buffer=shm.buf))
        finally:
            shm.close()
            shm.unlink()
        return Tensor(arr) if (tensorify or obj.was_tensor) else arr
    if isinstance(obj, _TensorArray):
        return Tensor(obj.arr)
    if isinstance(obj, np.ndarray):
        return Tensor(obj) if tensorify else obj
    if isinstance(obj, (list, tuple)):
        return type(obj)(_from_transport(o, tensorify) for o in obj)
    if isinstance(obj, dict):
        return {k: _from_transport(v, tensorify) for k, v in obj.items()}
    return obj


def _mp_worker_main(result_q, worker_id, num_workers, dataset, collate,
                    my_batches, init_fn, use_shm, validate_pickle):
    """Worker process body: NUMPY work only — jax stays in the parent.

    ``validate_pickle`` (set for CUSTOM collates, whose outputs are not
    guaranteed ndarray-shaped): mp.Queue pickles in a background feeder
    thread where a PicklingError is unreachable, so the batch is dumped
    eagerly here first; an unpicklable batch becomes a structured
    _UnpicklableBatch signal instead of a mid-iteration crash."""
    _worker_info.info = _WorkerInfo(worker_id, num_workers, dataset)
    if init_fn is not None:
        init_fn(worker_id)
    try:
        for seq, batch_idx in my_batches:
            data = collate([dataset[i] for i in batch_idx])
            payload = _to_transport(data, use_shm)
            if validate_pickle:
                try:
                    blob = pickle.dumps(payload,
                                        protocol=pickle.HIGHEST_PROTOCOL)
                except Exception as e:  # noqa: BLE001
                    _release_transport(payload)
                    result_q.put((-2, _UnpicklableBatch(repr(e))))
                    return
                result_q.put((seq, _PickledBatch(blob)))
            else:
                result_q.put((seq, payload))
    except BaseException as e:  # noqa: BLE001 — ship it to the parent
        result_q.put((-1, _WorkerError(e)))


def default_collate_fn(batch: List[Any]):
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor(_stack_np([np.asarray(b._value) for b in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(_stack_np(batch))
    if isinstance(sample, (int, float, np.number)):
        return Tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        transposed = zip(*batch)
        return type(sample)(default_collate_fn(list(items)) for items in transposed)
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    return batch


def _stack_np(arrays):
    """np.stack with the parallel C++ collate for big batches (io/native.py;
    the reference's C++ reader does the same fan-in off the GIL)."""
    from .native import native_stack

    out = native_stack(arrays)
    return out if out is not None else np.stack(arrays)


def _contains_tensor(obj) -> bool:
    if isinstance(obj, Tensor):
        return True
    if isinstance(obj, (list, tuple)):
        return any(_contains_tensor(o) for o in obj)
    if isinstance(obj, dict):
        return any(_contains_tensor(v) for v in obj.values())
    return False


def _np_collate(batch: List[Any]):
    """default_collate_fn's numpy twin for worker processes: identical
    structure, but NO jax arrays are created off the main process."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return _stack_np([np.asarray(b._value) for b in batch])
    if isinstance(sample, np.ndarray):
        return _stack_np(batch)
    if isinstance(sample, (int, float, np.number)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        transposed = zip(*batch)
        return type(sample)(_np_collate(list(items)) for items in transposed)
    if isinstance(sample, dict):
        return {k: _np_collate([b[k] for b in batch]) for k in sample}
    return batch


class DataLoader:
    """reference: `io/dataloader/dataloader_iter.py` — process workers with
    shared-memory transport by default, falling back to a thread-pool
    prefetcher with an ordered output queue.

    Notes on the process path: the parent issues ONE extra
    ``dataset[first_index]`` call per DataLoader (cached) to probe whether
    items contain Tensors (jax work is unsafe in forked workers — such
    datasets stay on threads); custom-collate batches must survive pickling
    through the mp queue — an unpicklable batch triggers a logged
    thread-pool fallback at epoch start (mid-epoch it raises, telling you
    to set ``use_process_workers=False``)."""

    def __init__(self, dataset, feed_list=None, places=None, return_list: bool = True,
                 batch_sampler=None, batch_size: int = 1, shuffle: bool = False,
                 drop_last: bool = False, collate_fn=None, num_workers: int = 0,
                 use_buffer_reader: bool = True, prefetch_factor: int = 2,
                 use_shared_memory: bool = True, timeout: int = 0, worker_init_fn=None,
                 persistent_workers: bool = False, use_process_workers: bool = True):
        self.dataset = dataset
        self.num_workers = num_workers
        self.collate_fn = collate_fn or default_collate_fn
        self.prefetch_factor = prefetch_factor
        self.use_shared_memory = use_shared_memory
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.use_process_workers = use_process_workers
        self._tensor_items: Optional[bool] = None
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size, drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def state_dict(self) -> dict:
        """Resumable data-stream position (delegates to the batch
        sampler) — include it in the training checkpoint payload so a
        post-rewind resume is deterministic in the data stream.
        IterableDataset loaders have no position: empty dict. Position
        counts batches DELIVERED to the consumer — exact for the sync
        path, and re-tracked per delivery under prefetching workers
        (batches a worker computed ahead but never handed over do not
        count as consumed)."""
        bs = self.batch_sampler
        if bs is None or not hasattr(bs, "state_dict"):
            return {}
        return bs.state_dict()

    def set_state_dict(self, state: dict) -> None:
        bs = self.batch_sampler
        if state and bs is not None and hasattr(bs, "set_state_dict"):
            bs.set_state_dict(state)

    def __iter__(self) -> Iterator:
        if self._iterable_mode:
            return self._iter_iterable()
        if self.num_workers == 0:
            return self._iter_sync()
        # the prefetching paths materialize the epoch's index list up
        # front, which runs the sampler's own position tracking to
        # exhaustion — re-track position at DELIVERY granularity so
        # state_dict() stays exact (and rewind fast-forward lands on the
        # right batch) under workers too
        start = getattr(self.batch_sampler, "_resume_from", 0)
        if self.use_process_workers:
            try:
                gen = self._iter_processes()  # spawn failures surface HERE
            except (ImportError, OSError, ValueError, AttributeError,
                    TypeError, pickle.PicklingError) as e:
                import logging

                logging.getLogger("paddle_tpu.io").warning(
                    "process workers unavailable (%s); falling back to "
                    "threads", e)
                # the failed process path already consumed the sampler's
                # resume offset when it materialized the index list —
                # restore it so the threaded re-list resumes at the same
                # batch instead of replaying the epoch head
                if hasattr(self.batch_sampler, "_resume_from"):
                    self.batch_sampler._resume_from = start
            else:
                return self._track_position(self._wrap_process_iter(gen),
                                            start)
        return self._track_position(self._iter_threaded(), start)

    def _track_position(self, gen, start: int):
        """Mirror delivered-batch count into the batch sampler's position
        (its own counter was exhausted by the up-front materialization)."""
        bs = self.batch_sampler
        n = start
        for item in gen:
            n += 1
            bs._position = n
            yield item
        bs._position = 0  # epoch delivered in full

    def _wrap_process_iter(self, gen):
        """Mid-iteration escape hatch: a worker that produced an
        unpicklable custom-collate batch signals _PicklingFallback — rerun
        the epoch on the threaded pool if nothing was yielded yet."""
        yielded = 0
        try:
            for item in gen:
                yield item
                yielded += 1
        except _PicklingFallback as e:
            if yielded:
                raise RuntimeError(
                    f"DataLoader custom collate produced an unpicklable "
                    f"batch after {yielded} batches were already delivered "
                    f"({e}); cannot fall back to threads mid-epoch — set "
                    "use_process_workers=False") from e
            import logging

            logging.getLogger("paddle_tpu.io").warning(
                "custom collate output not picklable (%s); falling back "
                "to threads", e)
            # reuse the indices the process path already materialized — a
            # one-shot (generator) batch_sampler must not be iterated twice
            yield from self._iter_threaded(indices=self._mp_indices)

    def _iter_sync(self):
        for batch_idx in self.batch_sampler:
            yield self.collate_fn([self.dataset[i] for i in batch_idx])

    def _iter_iterable(self):
        batch = []
        for item in self.dataset:
            batch.append(item)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch)

    def _iter_processes(self):
        """Worker processes + shared-memory ndarray transport (reference
        `dataloader_iter.py:358`). Workers execute dataset[i] + collate as
        NUMPY work; the parent re-wraps arrays as Tensors. fork context when
        available (no pickling of the dataset), spawn otherwise."""
        indices = self._mp_indices = list(self.batch_sampler)
        if not indices:
            return iter(())
        nw = min(self.num_workers, len(indices))
        # datasets whose items are Tensors (jax arrays) would make the
        # FORKED child do device transfers against the parent's inherited,
        # post-fork-inconsistent XLA runtime — probe one sample (cached:
        # this is a property of the dataset, and __getitem__ may be an
        # expensive decode) and keep such datasets on the threaded pool
        if self._tensor_items is None:
            self._tensor_items = _contains_tensor(self.dataset[indices[0][0]])
        if self._tensor_items:
            raise TypeError(
                "dataset items contain Tensors; jax work is unsafe in "
                "forked workers — using threads (return numpy from "
                "__getitem__ to enable process workers)")
        try:
            ctx = multiprocessing.get_context("fork")
        except ValueError:
            ctx = multiprocessing.get_context("spawn")
        collate = _np_collate if self.collate_fn is default_collate_fn \
            else self.collate_fn
        result_q = ctx.Queue(maxsize=max(2, nw * self.prefetch_factor))
        procs = []
        # spawn eagerly so start/pickling failures surface in __iter__ (where
        # the threaded fallback catches them), not at first next(); a partial
        # spawn must not leave earlier workers computing into an abandoned
        # queue
        try:
            for w in range(nw):
                my = [(i, b) for i, b in enumerate(indices) if i % nw == w]
                p = ctx.Process(
                    target=_mp_worker_main,
                    args=(result_q, w, nw, self.dataset, collate, my,
                          self.worker_init_fn, self.use_shared_memory,
                          collate is not _np_collate),
                    daemon=True)
                p.start()
                procs.append(p)
        except BaseException:
            for p in procs:
                p.terminate()
            raise
        return self._consume_process_results(procs, result_q, len(indices),
                                             collate is _np_collate)

    def _consume_process_results(self, procs, result_q, total, tensorify):
        try:
            buffered = {}
            next_seq = 0
            deadline_step = self.timeout or 5.0
            while next_seq < total:
                while next_seq in buffered:
                    yield _from_transport(buffered.pop(next_seq), tensorify)
                    next_seq += 1
                if next_seq >= total:
                    break
                try:
                    seq, data = result_q.get(timeout=deadline_step)
                except queue.Empty:
                    if self.timeout:
                        raise RuntimeError(
                            f"DataLoader worker timed out after "
                            f"{self.timeout}s (batch {next_seq})")
                    if not any(p.is_alive() for p in procs) and \
                            result_q.empty():
                        raise RuntimeError(
                            "DataLoader worker processes died without "
                            "delivering all batches (check workerlog / "
                            "OOM killer)")
                    continue
                if isinstance(data, _UnpicklableBatch):
                    raise _PicklingFallback(data.reason)
                if isinstance(data, _WorkerError):
                    raise RuntimeError(
                        f"DataLoader worker raised {data.type_name}:\n"
                        f"{data.formatted}")
                if isinstance(data, _PickledBatch):
                    data = pickle.loads(data.blob)
                buffered[seq] = data
        finally:
            for p in procs:
                if p.is_alive():
                    p.terminate()
            for p in procs:
                p.join(timeout=2.0)
            # early exit / worker error: unlink any shared-memory segments
            # still parked in unconsumed batches, or /dev/shm leaks one
            # segment per abandoned batch for the life of the process
            def _release(payload):
                if isinstance(payload, _PickledBatch):
                    try:  # shm descriptors live inside the pickled blob
                        payload = pickle.loads(payload.blob)
                    except Exception:
                        return
                _release_transport(payload)

            for payload in buffered.values():
                _release(payload)
            while True:
                try:
                    _, payload = result_q.get_nowait()
                except (queue.Empty, OSError, ValueError):
                    break
                _release(payload)

    def _iter_threaded(self, indices=None):
        if indices is None:
            indices = list(self.batch_sampler)
        results: "queue.Queue" = queue.Queue(maxsize=self.num_workers * self.prefetch_factor)
        done = object()

        def worker(worker_id, my_batches):
            _worker_info.info = _WorkerInfo(worker_id, self.num_workers, self.dataset)
            if self.worker_init_fn is not None:
                self.worker_init_fn(worker_id)
            for seq, batch_idx in my_batches:
                try:
                    data = self.collate_fn([self.dataset[i] for i in batch_idx])
                except BaseException as e:  # propagate to the consumer, don't hang it
                    results.put((seq, e))
                    return
                results.put((seq, data))

        threads = []
        for w in range(self.num_workers):
            my = [(i, b) for i, b in enumerate(indices) if i % self.num_workers == w]
            t = threading.Thread(target=worker, args=(w, my), daemon=True)
            t.start()
            threads.append(t)

        buffered = {}
        next_seq = 0
        total = len(indices)
        while next_seq < total:
            while next_seq in buffered:
                data = buffered.pop(next_seq)
                if isinstance(data, BaseException):
                    raise data
                yield data
                next_seq += 1
            if next_seq >= total:
                break
            seq, data = results.get()
            buffered[seq] = data
        for t in threads:
            t.join(timeout=1.0)
