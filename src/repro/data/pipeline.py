"""Data pipeline: prefetch, device_put with sharding, stage-aware resizing.

A thin production-style wrapper over the deterministic synthetic sources:
  * host-sharded batches (each host generates only its slice)
  * optional device placement with a NamedSharding (global arrays)
  * stage switching (mixed-batch training changes (batch, seq) mid-run)

Placement: pass either an explicit ``sharding`` (applied to every leaf) or a
``mesh`` — with a mesh, batches are split over its data axes
(``sharding.batch_sharding``), which is exactly the layout the sharded train
step declares via ``in_shardings``, so the jit boundary never reshards.

Each prefetched batch shows in a profiler trace as ``data.next`` (the
source) and ``data.place`` (its ``device_put``).
"""
from __future__ import annotations

import collections
from typing import Dict, Iterator, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.synthetic import batch_iterator
from repro.sharding.axes import batch_axes, dp_size
from repro.sharding.placement import batch_sharding
from repro.telemetry.spans import trace_span


class DataPipeline:
    def __init__(
        self,
        cfg: ModelConfig,
        batch: int,
        seq: int,
        *,
        seed: int = 0,
        sharding=None,
        mesh=None,
        prefetch: int = 2,
    ):
        if mesh is not None and sharding is None:
            dp = dp_size(mesh)
            if batch % dp:
                raise ValueError(
                    f"batch {batch} is not divisible by the mesh's "
                    f"data-parallel size {dp} (axes {batch_axes(mesh)})"
                )
            sharding = batch_sharding(mesh)
        self.cfg = cfg
        self.batch = batch
        self.seq = seq
        self.seed = seed
        self.sharding = sharding
        self.prefetch = prefetch
        self._it = batch_iterator(
            cfg, batch, seq, seed=seed,
            host_index=jax.process_index(), host_count=jax.process_count(),
        )
        self._buf: collections.deque = collections.deque()

    def _fill(self):
        while len(self._buf) < self.prefetch:
            with trace_span("data.next"):
                b = next(self._it)
            if self.sharding is not None:
                with trace_span("data.place"):
                    b = jax.tree.map(
                        lambda x, s=self.sharding: jax.device_put(x, s), b
                    )
            self._buf.append(b)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        self._fill()
        return self._buf.popleft()

    def with_stage(self, batch: int, seq: int) -> "DataPipeline":
        """New pipeline for a mixed-batch stage (fresh shapes, same source)."""
        return DataPipeline(
            self.cfg, batch, seq, seed=self.seed,
            sharding=self.sharding, prefetch=self.prefetch,
        )
