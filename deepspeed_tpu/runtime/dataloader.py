"""Deterministic micro-batch data loading.

Analog of reference ``deepspeed/runtime/dataloader.py`` (``DeepSpeedDataLoader:33``,
``RepeatingLoader:10``). The reference wraps a torch DataLoader with a
DistributedSampler; here the loader yields *global* host batches (numpy
pytrees) that ``engine.shard_batch`` lays out over the mesh — under pjit the
"distributed sampler" is simply the dp sharding of the batch dimension.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable, Iterator, Optional, Sequence

import numpy as np


class RepeatingLoader:
    """Wraps an iterator to restart on StopIteration (reference dataloader.py:10)."""

    def __init__(self, loader: Iterable):
        self.loader = loader
        self.data_iter = iter(self.loader)

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self.data_iter)
        except StopIteration:
            self.data_iter = iter(self.loader)
            return next(self.data_iter)


def default_collate(samples: Sequence[Any]):
    """Stack a list of sample pytrees (dicts/tuples of arrays) into one batch."""
    first = samples[0]
    if isinstance(first, dict):
        return {k: default_collate([s[k] for s in samples]) for k in first}
    if isinstance(first, (tuple, list)):
        return type(first)(default_collate([s[i] for s in samples]) for i in range(len(first)))
    return np.stack([np.asarray(s) for s in samples])


class DeepSpeedDataLoader:
    """Batches an indexable dataset into global train batches.

    Deterministic shuffling per epoch via a seeded permutation, matching the
    reference's ``data_sampler`` determinism guarantees.
    """

    def __init__(
        self,
        dataset,
        batch_size: int,
        collate_fn: Optional[Callable] = None,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.collate_fn = collate_fn or default_collate
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.epoch = 0
        n = len(dataset)
        self.len = n // batch_size if drop_last else (n + batch_size - 1) // batch_size

    def __len__(self):
        return self.len

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def __iter__(self) -> Iterator[Any]:
        n = len(self.dataset)
        order = np.arange(n)
        if self.shuffle:
            order = np.random.RandomState(self.seed + self.epoch).permutation(n)
        for b in range(self.len):
            idx = order[b * self.batch_size : (b + 1) * self.batch_size]
            yield self.collate_fn([self.dataset[int(i)] for i in idx])
        self.epoch += 1


class DevicePrefetchLoader:
    """Async H2D prefetch: keep ``depth`` batches already resident on device.

    Reference analog: the CUDA-stream input pipelining DeepSpeed gets for
    free from torch DataLoader ``pin_memory`` + non-blocking copies. Under
    JAX, ``jax.device_put`` is async — dispatching the NEXT batch's transfer
    before blocking on the current step overlaps H2D with compute, removing
    the per-step upload from the critical path.

    ``put`` maps a host pytree to device arrays (typically
    ``engine.shard_batch``).
    """

    def __init__(self, loader: Iterable, put: Callable[[Any], Any], depth: int = 2):
        assert depth >= 1
        self.loader = loader
        self.put = put
        self.depth = depth

    def __iter__(self) -> Iterator[Any]:
        import collections

        queue: "collections.deque" = collections.deque()
        it = iter(self.loader)
        try:
            while len(queue) < self.depth:
                queue.append(self.put(next(it)))
        except StopIteration:
            pass
        while queue:
            out = queue.popleft()
            try:
                queue.append(self.put(next(it)))
            except StopIteration:
                pass
            yield out
