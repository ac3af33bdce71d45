"""DataLoader.

Reference analog: python/paddle/fluid/reader.py:311 (DataLoader) +
dataloader_iter.py:162/:370 (single/multi-process iterators with worker
processes and shared-memory LoDTensor transport over a C++ blocking queue).

TPU-native: workers are multiprocessing processes producing numpy batches
into an mp.Queue (kernel shared memory transport); a prefetch thread keeps
`prefetch_factor` batches decoded ahead. Batches convert to Tensors on
yield; XLA transfers them on first use.
"""
from __future__ import annotations

import itertools
import queue as queue_mod
import threading
import multiprocessing as mp
from typing import Callable, Optional

import numpy as np

from ..core.tensor import Tensor, to_tensor
from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler

_worker_info = threading.local()


class WorkerInfo:
    def __init__(self, id, num_workers, dataset, seed):  # noqa: A002
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


def get_worker_info():
    return getattr(_worker_info, "info", None)


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, (np.ndarray, np.generic)):
        return np.stack(batch)
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s._array) for s in batch])
    if isinstance(sample, (int, float)):
        return np.asarray(batch)
    if isinstance(sample, (str, bytes)):
        return batch
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch]) for k in sample}
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return type(sample)(default_collate_fn(list(t)) for t in transposed)
    return batch


def _to_tensor_tree(data):
    if isinstance(data, np.ndarray):
        return to_tensor(data)
    if isinstance(data, dict):
        return {k: _to_tensor_tree(v) for k, v in data.items()}
    if isinstance(data, (list, tuple)):
        return type(data)(_to_tensor_tree(v) for v in data)
    return data


def _worker_loop(dataset, index_queue, data_queue, collate_fn, worker_id,
                 num_workers, base_seed, init_fn=None, shm_cfg=None):
    _worker_info.info = WorkerInfo(worker_id, num_workers, dataset,
                                   base_seed + worker_id)
    np.random.seed(base_seed + worker_id)
    if init_fn is not None:
        init_fn(worker_id)
    shm = None
    slot_bytes = 0
    if shm_cfg is not None:
        from ..core.native import ShmQueue
        name, slot_bytes, n_slots = shm_cfg
        try:
            shm = ShmQueue(name, n_slots=n_slots, slot_bytes=slot_bytes,
                           owner=False)
        except Exception:
            shm = None

    def emit(payload):
        # native shm ring when attached; batches bigger than a slot take
        # the mp.Queue path behind a marker so pop order stays defined
        if shm is not None:
            import pickle
            raw = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
            if len(raw) <= slot_bytes:
                shm.put(raw)
                return
            shm.put(pickle.dumps(("__big__", payload[0])))
        data_queue.put(payload)

    while True:
        item = index_queue.get()
        if item is None:
            break
        batch_id, indices = item
        try:
            samples = [dataset[i] for i in indices]
            emit((batch_id, collate_fn(samples), None))
        except Exception as e:  # propagate worker errors
            emit((batch_id, None, e))


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.return_list = return_list
        # `places` pins output batches to a device; with use_buffer_reader
        # the transfer double-buffers ahead of the consumer (the pinned
        # buffered_reader analog — see io/device_loader.py)
        self.places = places if isinstance(places, (list, tuple, type(None))) \
            else [places]
        self.use_buffer_reader = use_buffer_reader
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.use_shared_memory = use_shared_memory
        self._is_iterable = isinstance(dataset, IterableDataset)
        # sample-exact resume bookkeeping: the sampler state at epoch
        # start plus a consumer-side yield count (see state_dict)
        self._active_state = None
        self._yielded = 0
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", batch_size)
        elif not self._is_iterable:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)
            self.batch_size = batch_size
        else:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last

    def __len__(self):
        if self._is_iterable:
            raise TypeError("IterableDataset has no fixed length")
        return len(self.batch_sampler)

    def __iter__(self):
        if self.batch_sampler is not None and \
                hasattr(self.batch_sampler, "state_dict"):
            # snapshot BEFORE any dispatch: _iter_multi materializes the
            # whole sampler upfront for its prefetch workers, which runs
            # the sampler's own cursor to epoch end immediately
            self._active_state = dict(self.batch_sampler.state_dict())
            self._yielded = 0
        if self._is_iterable:
            it = self._iter_iterable()
        elif self.num_workers == 0:
            it = self._iter_single()
        else:
            it = self._iter_multi()
        if self.places:
            if len(self.places) > 1:
                raise ValueError(
                    "multi-place DataLoader output is not supported: one "
                    "jax client owns all local chips, so in-host data "
                    "parallelism is expressed by sharding the batch over "
                    "a mesh (device_put with a distributed.NamedSharding "
                    "over the 'dp' axis), not by per-place feeding")
            from .device_loader import DeviceDataLoader
            buf = self.prefetch_factor if self.use_buffer_reader else 1
            it = iter(DeviceDataLoader(it, self.places[0], buffer_size=buf))
        return self._instrumented(it)

    def _instrumented(self, it):
        """Telemetry around next-batch: a host span when a profiler is
        live, and fetch-latency histogram + batch counter when
        FLAGS_tpu_metrics is on. Fetch time here is consumer-side stall
        — with prefetch ahead of the consumer it should stay near zero;
        a hot dataloader_next_seconds histogram means input-bound.

        Also the resume cursor's counting point: a batch counts as
        consumed the moment it is handed to the consumer (who will train
        on it before checkpointing), NOT when a prefetch worker decodes
        it — so ``state_dict`` stays exact however far prefetch ran
        ahead."""
        import time as _time
        from ..profiler import _record_span, metrics as _metrics
        try:
            while True:
                rec = _metrics.enabled()
                t0 = _time.perf_counter() if rec else None
                try:
                    with _record_span("dataloader_next"):
                        batch = next(it)
                except StopIteration:
                    self._active_state = None  # epoch drained cleanly
                    return
                if rec:
                    _metrics.counter("dataloader_batches_total",
                                     "Batches yielded by DataLoader").inc()
                    _metrics.histogram(
                        "dataloader_next_seconds",
                        "Consumer-side wait per batch").observe(
                            _time.perf_counter() - t0)
                self._yielded += 1
                yield batch
        finally:
            # an early consumer break must tear down worker processes
            # now, not at GC time (the inner generator's finally owns
            # the worker/shm cleanup)
            close = getattr(it, "close", None)
            if close is not None:
                close()

    # -- sample-exact resume ------------------------------------------------
    def state_dict(self) -> dict:
        """The resume cursor (epoch + consumed GLOBAL sample offset +
        shuffle RNG derivation), exact mid-epoch: the sampler state
        snapshotted at epoch start advanced by the batches actually
        handed to the consumer. Requires a batch_sampler with
        ``state_dict`` (DistributedBatchSampler); CheckpointManager
        embeds this in every commit manifest via ``attach_data`` and
        replays it on restore."""
        bs = self.batch_sampler
        if bs is None or not hasattr(bs, "state_dict"):
            raise TypeError(
                "DataLoader.state_dict needs a batch_sampler exposing "
                "state_dict/load_state_dict (io.DistributedBatchSampler); "
                f"got {type(bs).__name__}")
        if self._active_state is None:
            return dict(bs.state_dict())
        st = dict(self._active_state)
        gbs = int(st.get("global_batch_size",
                         getattr(bs, "global_batch_size", self.batch_size)))
        st["offset"] = int(st.get("offset", 0)) + self._yielded * gbs
        return st

    def load_state_dict(self, state: dict):
        """Resume the underlying sampler from a cursor — valid across an
        elastic dp resize because offsets are in global sample order."""
        bs = self.batch_sampler
        if bs is None or not hasattr(bs, "load_state_dict"):
            raise TypeError(
                "DataLoader.load_state_dict needs a batch_sampler exposing "
                "state_dict/load_state_dict (io.DistributedBatchSampler); "
                f"got {type(bs).__name__}")
        bs.load_state_dict(dict(state))
        self._active_state = None
        self._yielded = 0

    def _iter_iterable(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                yield _to_tensor_tree(self.collate_fn(batch))
                batch = []
        if batch and not getattr(self, "drop_last", False):
            yield _to_tensor_tree(self.collate_fn(batch))

    def _iter_single(self):
        for indices in self.batch_sampler:
            samples = [self.dataset[i] for i in indices]
            yield _to_tensor_tree(self.collate_fn(samples))

    def _start_context(self):
        """Pick the worker start method (cached after the first call —
        picklability of the payload cannot change between epochs).

        spawn by default: the parent holds a live multithreaded XLA/PJRT
        client, and forking it risks the TSL "Expected N threads to join"
        abort at shutdown (reference analog keeps fork because its C++
        runtime is fork-aware; ours is not). NOTE: spawn re-imports
        __main__ in each worker, so scripts that iterate a
        num_workers>0 DataLoader at module top level need the standard
        ``if __name__ == "__main__"`` guard. Fork remains a fallback for
        datasets/collate_fns that cannot pickle (e.g. defined in a local
        scope), with a warning.
        """
        if getattr(self, "_mp_ctx", None) is not None:
            return self._mp_ctx
        import os
        import pickle
        import sys
        import warnings

        class _NullWriter:
            def write(self, _):
                pass  # probe picklability without materializing bytes

        reason = None
        # spawn re-executes __main__: piped/stdin scripts have no real
        # file to re-run and every worker would die at startup
        main_file = getattr(sys.modules.get("__main__"), "__file__", None)
        if main_file is not None and not os.path.exists(main_file):
            reason = (f"__main__ has no importable file ({main_file!r}; "
                      "stdin/exec script)")
        if reason is None:
            try:
                pickle.Pickler(_NullWriter(), pickle.HIGHEST_PROTOCOL).dump(
                    (self.dataset, self.collate_fn, self.worker_init_fn))
            except Exception:
                reason = "worker payload is not picklable"
        if reason is None:
            self._mp_ctx = mp.get_context("spawn")
        else:
            warnings.warn(
                f"DataLoader: {reason}; falling back to fork workers. "
                "Forking a process with a live JAX client can deadlock or "
                "abort at shutdown — run from a real script file with the "
                "dataset/collate_fn at module scope to enable spawn "
                "workers.", RuntimeWarning, stacklevel=3)
            self._mp_ctx = mp.get_context("fork")
        return self._mp_ctx

    @staticmethod
    def _worker_child_env():
        """Env overrides for worker children: workers only produce numpy
        batches, and a chip belongs to one process — pin jax to cpu in
        case anything in a worker imports it."""
        return {"JAX_PLATFORMS": "cpu"}

    def _iter_multi(self):
        import os as _os
        ctx = self._start_context()
        index_queues = []
        data_queue = ctx.Queue()
        workers = []
        base_seed = np.random.randint(0, 2 ** 31 - 1)

        # native shared-memory transport (reference: C++ blocking_queue +
        # shared-mem tensor transport) when built; mp.Queue otherwise
        shm = None
        shm_cfg = None
        if self.use_shared_memory:
            from ..core import native
            if native.available():
                import os as _os
                name = f"/ptq_dl_{_os.getpid()}_{id(self) & 0xffffff}"
                slot_bytes = 32 << 20
                n_slots = max(4, self.num_workers * self.prefetch_factor)
                try:
                    shm = native.ShmQueue(name, n_slots=n_slots,
                                          slot_bytes=slot_bytes, owner=True)
                    shm_cfg = (name, slot_bytes, n_slots)
                except Exception:
                    shm = None

        # apply child-env overrides around start(): both fork and spawn
        # children inherit os.environ as of start() time. Snapshot the
        # environment ONCE before the loop (a per-key environ.get is an
        # env lookup per iteration).
        env_before = dict(_os.environ)
        saved_env = {}
        for k, v in self._worker_child_env().items():
            saved_env[k] = env_before.get(k)
            if v is None:
                _os.environ.pop(k, None)
            else:
                _os.environ[k] = v
        try:
            for wid in range(self.num_workers):
                iq = ctx.Queue()
                w = ctx.Process(
                    target=_worker_loop,
                    args=(self.dataset, iq, data_queue, self.collate_fn, wid,
                          self.num_workers, base_seed, self.worker_init_fn,
                          shm_cfg),
                    daemon=True)
                w.start()
                workers.append(w)
                index_queues.append(iq)
        finally:
            for k, old in saved_env.items():
                if old is None:
                    _os.environ.pop(k, None)
                else:
                    _os.environ[k] = old

        def recv():
            # Poll with short sleeps instead of blocking indefinitely in
            # the transport: a worker that died (bad __main__ under
            # spawn, OOM-killed, segfault) must surface as an error, not
            # an eternal hang on an empty queue. Reads next_yield/
            # next_dispatch/reorder from the enclosing scope to decide
            # whether a dead worker actually stalls the pipeline.
            import time
            deadline = (time.monotonic() + self.timeout) if self.timeout \
                else None
            wait = 1e-4
            want_big = None  # batch id promised on data_queue via marker
            while True:
                if shm is None or want_big is not None:
                    try:
                        return data_queue.get(timeout=0.2)
                    except queue_mod.Empty:
                        pass
                elif shm.qsize() > 0:
                    import pickle
                    payload = pickle.loads(shm.get())
                    if isinstance(payload, tuple) and len(payload) == 2 \
                            and payload[0] == "__big__":
                        want_big = payload[1]
                        continue
                    return payload
                dead = {i for i, w in enumerate(workers)
                        if not w.is_alive()}
                if dead:
                    # stall = some batch we still need is owned by a dead
                    # worker (round-robin: batch i -> worker i % N); an
                    # idle worker dying after finishing its share must
                    # not abort an epoch the others can complete
                    if want_big is not None:
                        stalled = (want_big % self.num_workers) in dead
                    else:
                        stalled = any(
                            (i % self.num_workers) in dead
                            for i in range(next_yield, next_dispatch)
                            if i not in reorder)
                    if stalled and (shm is None or shm.qsize() == 0):
                        # grace drain: the dying worker may have flushed
                        # its batch into the pipe first. A large batch
                        # (or a loaded host) can take several seconds to
                        # land, so drain over a window — a single 1s get
                        # aborted recoverable epochs. The user's timeout
                        # stays authoritative: the window never extends
                        # past `deadline`.
                        grace_end = time.monotonic() + min(
                            self.timeout or 5.0, 10.0)
                        if deadline is not None:
                            grace_end = min(grace_end, deadline)
                        while True:
                            try:
                                return data_queue.get(timeout=0.5)
                            except queue_mod.Empty:
                                if time.monotonic() < grace_end:
                                    continue
                                if deadline is not None and \
                                        time.monotonic() > deadline:
                                    raise TimeoutError(
                                        f"DataLoader timed out after "
                                        f"{self.timeout}s waiting for a "
                                        "worker batch (worker(s) "
                                        f"{sorted(dead)} dead)") from None
                                dw = [workers[i] for i in sorted(dead)]
                                raise RuntimeError(
                                    "DataLoader worker(s) "
                                    f"{[w.pid for w in dw]} exited "
                                    "unexpectedly (exitcodes "
                                    f"{[w.exitcode for w in dw]}) "
                                    "with batches still pending") from None
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        f"DataLoader timed out after {self.timeout}s "
                        "waiting for a worker batch")
                if shm is not None and want_big is None:
                    time.sleep(wait)
                    wait = min(wait * 2, 0.005)

        try:
            batches = list(self.batch_sampler)
            # dispatch round-robin with bounded in-flight count
            inflight = 0
            next_dispatch = 0
            reorder = {}
            next_yield = 0
            max_inflight = self.num_workers * self.prefetch_factor
            while next_yield < len(batches):
                while next_dispatch < len(batches) and inflight < max_inflight:
                    index_queues[next_dispatch % self.num_workers].put(
                        (next_dispatch, batches[next_dispatch]))
                    next_dispatch += 1
                    inflight += 1
                bid, data, err = recv()
                if err is not None:
                    raise err
                inflight -= 1
                reorder[bid] = data
                while next_yield in reorder:
                    yield _to_tensor_tree(reorder.pop(next_yield))
                    next_yield += 1
        finally:
            for iq in index_queues:
                iq.put(None)
            for w in workers:
                w.join(timeout=1)
                if w.is_alive():
                    w.terminate()
            if shm is not None:
                shm.close()
                shm.free()


def default_convert_fn(batch):
    """Convert without batching — the DataLoader's collate when
    batch_size=None (reference: fluid/dataloader/collate.py
    default_convert_fn)."""
    import numpy as _np
    from ..core.tensor import Tensor as _T
    if isinstance(batch, _T):
        return batch
    if isinstance(batch, _np.ndarray):
        return _T(jnp.asarray(batch))
    if isinstance(batch, (list, tuple)):
        return type(batch)(default_convert_fn(b) for b in batch)
    if isinstance(batch, dict):
        return {k: default_convert_fn(v) for k, v in batch.items()}
    return batch
