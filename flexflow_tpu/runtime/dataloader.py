"""Data loading.

Reference: per-app ``DataLoader`` (examples/cpp/AlexNet/alexnet.cc:145-343)
and the generic Python loaders (python/flexflow_dataloader.{h,cc,cu}).  The
reference pattern is: load the entire dataset once into host zero-copy
memory, then each ``next_batch`` index-launches a scatter of this batch's
samples into the input tensor's partition.

TPU-native: the full dataset stays in host numpy (the ZC-memory analogue);
``next_batch`` slices the next batch and ``jax.device_put``s it directly
with the input tensor's NamedSharding, so each chip receives exactly its
shard over PCIe/DMA — the analogue of the per-GPU scatter task.  A
synthetic mode generates the dataset once from a fixed seed (the
reference's primary benchmark fixture, alexnet.cc:152-155).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from ..tensor import DataType, Tensor
from .profiling import span


class DataLoader:
    """Generic multi-input loader (analogue of SingleDataLoader /
    ImgDataLoader in python/flexflow_dataloader.cc plus the per-app C++
    loaders)."""

    def __init__(self, ff, inputs: Dict[Tensor, np.ndarray],
                 labels: np.ndarray, shuffle: bool = False, seed: int = 0,
                 prefetch: bool = True):
        self.ff = ff
        self.inputs = {t: np.ascontiguousarray(self._to_native(t, a))
                       for t, a in inputs.items()}
        self.labels = np.ascontiguousarray(labels)
        sizes = {a.shape[0] for a in self.inputs.values()} | {labels.shape[0]}
        if len(sizes) != 1:
            raise ValueError(f"inconsistent sample counts: {sizes}")
        self.num_samples = labels.shape[0]
        self.batch_size = ff.config.batch_size
        self.shuffle = shuffle
        self._rng = np.random.default_rng(seed)
        self._order = np.arange(self.num_samples)
        self.next_index = 0
        # Double buffering: the NEXT batch's host gather AND its sharded
        # jax.device_put both run on a worker thread while the device
        # computes the current step (the reference's scatter index-launch
        # likewise overlaps with compute under Legion's dependence
        # analysis).  set_batch sees committed jax.Arrays and passes them
        # through, so the host->device copy overlaps the running step
        # instead of serializing inside next_batch.  Host-embedding index
        # inputs stay numpy (set_batch keeps a host copy for the sparse
        # gather), as does anything staging can't place — it falls back
        # to the raw gather result.
        self.prefetch = prefetch
        self._pool = None
        self._pending = None   # (start_index, order_version, future)
        self._order_version = 0

    @staticmethod
    def _to_native(t: Tensor, a: np.ndarray) -> np.ndarray:
        """Accept reference-layout (NCHW) image datasets and convert once
        to the framework's NHWC layout on host."""
        if a.ndim == 4 and len(t.dims) == 4 and a.shape[1:] != t.dims[1:]:
            n, c, h, w = a.shape
            if (h, w, c) == tuple(t.dims[1:]):
                return a.transpose(0, 2, 3, 1)
        return a

    @classmethod
    def synthetic(cls, ff, input_tensor: Tensor, label_tensor: Optional[Tensor] = None,
                  num_samples: Optional[int] = None, num_classes: int = 10,
                  seed: int = 17) -> "DataLoader":
        """Random dataset generated once (reference synthetic mode)."""
        label_tensor = label_tensor or ff.label_tensor
        num_samples = num_samples or ff.config.batch_size
        rng = np.random.default_rng(seed)
        x = rng.standard_normal((num_samples,) + tuple(input_tensor.dims[1:]),
                                dtype=np.float32)
        if label_tensor.dtype == DataType.INT32:
            y = rng.integers(0, num_classes,
                             size=(num_samples,) + tuple(label_tensor.dims[1:]),
                             dtype=np.int32)
        else:
            y = rng.standard_normal((num_samples,) + tuple(label_tensor.dims[1:]),
                                    dtype=np.float32)
        return cls(ff, {input_tensor: x}, y)

    def reset(self) -> None:
        self.next_index = 0
        if self.shuffle:
            self._rng.shuffle(self._order)
        self._order_version += 1   # invalidate any prefetched batch
        self._pending = None

    def num_batches(self) -> int:
        return self.num_samples // self.batch_size

    def skip_batches(self, n: int) -> None:
        """Advance the epoch's cursor by ``n`` batches WITHOUT gathering
        or staging them — the shuffle-stream fast-forward a step-granular
        resume needs: after replaying completed epochs via ``reset()``,
        skipping the already-consumed batches lands the next
        ``next_batch`` on exactly the sample window the interrupted run
        would have seen (runtime/elastic.py)."""
        for _ in range(max(0, int(n))):
            self.next_index = self._start_of(self.next_index) + self.batch_size
        self._pending = None   # prefetched batch (if any) is now stale

    def _start_of(self, index: int) -> int:
        return 0 if index + self.batch_size > self.num_samples else index

    def _gather(self, start: int):
        from ..utils.native import gather_rows

        sel = self._order[start:start + self.batch_size]
        return ({t: gather_rows(a, sel) for t, a in self.inputs.items()},
                gather_rows(self.labels, sel))

    def _stage(self, start: int):
        """Worker-thread body: gather the batch, then pre-place each
        tensor on device with the same sharding set_batch would use
        (_place_batch passes committed arrays through untouched).  Any
        failure — model not compiled yet, no machine, odd tensor —
        degrades to handing set_batch the numpy batch, never an error
        on the worker thread."""
        xs, ys = self._gather(start)
        ff = self.ff
        try:
            from ..config import ParallelConfig

            he_keys = {info["input_key"]
                       for info in getattr(ff, "_host_embed", {}).values()}
            staged = {}
            for t, a in xs.items():
                if f"in_{t.guid}" in he_keys:
                    staged[t] = a  # set_batch keeps the host copy
                else:
                    staged[t] = ff._place_batch(a, ff._input_batch_degree(t))
            deg = getattr(ff.ops[-1], "pc", ParallelConfig(dims=(1,))).dims[0] \
                if ff.ops else 1
            return staged, ff._place_batch(ys, deg)
        except Exception:
            return xs, ys

    def next_batch(self, ff=None) -> None:
        ff = ff or self.ff
        chaos = getattr(ff, "_chaos", None)
        if chaos is not None:
            chaos.fire("data", model=ff)
        # Heartbeat BEFORE the gather (no-op unless FF_HEARTBEAT_PATH is
        # set): a wedged input pipeline gets named by the watchdog.
        from ..observability.health import write_heartbeat

        write_heartbeat("data_wait", step=getattr(ff, "_step_count", None))
        # "data_wait" = everything the step blocks on for input: the host
        # gather (~0 when the prefetch worker already has it) plus the
        # sharded device_put inside set_batch.
        with span(getattr(ff, "_telemetry", None), "data_wait",
                  batch_size=self.batch_size) as at:
            at["prefetched"] = (
                self._pending is not None
                and self._pending[0] == self._start_of(self.next_index)
                and self._pending[1] == self._order_version)
            self._next_batch_impl(ff)

    def _next_batch_impl(self, ff) -> None:
        start = self._start_of(self.next_index)
        batch = None
        if self._pending is not None:
            pstart, pver, fut = self._pending
            self._pending = None
            if pstart == start and pver == self._order_version:
                batch = fut.result()
        if batch is None:
            batch = self._gather(start)
        self.next_index = start + self.batch_size
        if self.prefetch:
            if self._pool is None:
                import concurrent.futures as cf

                self._pool = cf.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ff-dataloader")
            nxt = self._start_of(self.next_index)
            self._pending = (nxt, self._order_version,
                             self._pool.submit(self._stage, nxt))
        xs, ys = batch
        ff.set_batch(xs, ys)
