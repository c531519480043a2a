"""Token histogram (wordcount) — a non-astronomy job on the same engine.

Hadoop's canonical first job, run over the repo's LM data sources
(``data/pipeline.py``): map hashes each token to a partition, the shuffle
moves (optionally codec-compressed) token payloads, and the reduce bincounts
each partition's owned tokens — proving the Job API generalizes beyond the
paper's two astronomy apps while reusing the identical engine, codecs, and
``StageStats``/Amdahl accounting.

Wordcount is also the textbook map-side-combine job: its reduce is a
commutative-monoid fold over individual owned rows, so
``TokenHistogramReducer.combiner()`` returns a ``TokenCountCombiner`` and
the streaming executor (``mapreduce/executor.py``) pre-aggregates each split
to ``(token, count)`` rows BEFORE the shuffle — the wire then carries at
most ``min(split_rows, vocab)`` weighted entries instead of every token
occurrence, and only the combined [vocab] accumulator persists across
splits (out-of-core wordcount in O(vocab) device memory). The reducer
treats a second item column as an integer weight, so combined and raw
streams reduce through the same kernel and agree exactly.

Codec note: tokens ride the wire as float32 scalars. ``identity`` is exact;
``Int16Codec(max_abs=vocab)`` is *lossless* for integer tokens whenever
``vocab < 32767`` (quantization error < 0.5, removed by the reducer's
round()) — the LZO trade at its best: half the shuffle bytes, zero error.
(The combiner's count column is NOT generally in that domain — a count can
exceed ``vocab`` — which is why the executor only auto-derives combiners
for exact codecs.)
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.mapreduce.codecs import Int16Codec
from repro.mapreduce.executor import Combiner
from repro.mapreduce.job import (HashPartitioner, JobResult, MapReduceJob,
                                 Reducer, ShuffledData, run_job)


@dataclasses.dataclass(frozen=True)
class TokenCountCombiner(Combiner):
    """Map-side combine for the token histogram: rewrite a raw ``[n, 1]``
    token split into ``[m, 2]`` (token, count) rows — ``m`` = distinct
    in-range tokens present — before map/shuffle; per-split histogram
    partials then tree-sum across splits (the base ``combine``)."""

    vocab: int
    name: str = "token_count"

    def precombine(self, items: np.ndarray) -> np.ndarray:
        tok = np.rint(np.asarray(items, np.float64).reshape(-1)
                      ).astype(np.int64)
        tok = tok[(tok >= 0) & (tok < self.vocab)]
        counts = np.bincount(tok, minlength=self.vocab)
        nz = np.flatnonzero(counts)
        return np.stack([nz, counts[nz]], axis=1).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class TokenHistogramReducer(Reducer):
    """Per-partition bincount of owned tokens (padding rides as -1 on the
    host engine; masked by real counts on the device engine). Rows may
    carry a second column as an integer weight — that is how the map-side
    combiner's (token, count) streams reduce through the same kernel."""

    vocab: int
    pad_value: float = -1.0

    @staticmethod
    def _weights(owned, valid):
        if owned.shape[-1] > 1:
            return valid * jnp.round(owned[..., 1]).astype(jnp.int32)
        return valid

    def per_partition(self, owned_p, bucket_p):
        tok = jnp.round(owned_p[:, 0]).astype(jnp.int32)
        valid = ((tok >= 0) & (tok < self.vocab)).astype(jnp.int32)
        idx = jnp.clip(tok, 0, self.vocab - 1)
        return jnp.zeros((self.vocab,), jnp.int32).at[idx].add(
            self._weights(owned_p, valid))

    def reduce_partitions(self, owned, bucket, n_owned, n_bucket):
        tok = jnp.round(owned[..., 0]).astype(jnp.int32)      # [P, C1]
        valid = ((jnp.arange(tok.shape[1], dtype=jnp.int32)[None, :]
                  < n_owned[:, None])
                 & (tok >= 0) & (tok < self.vocab)).astype(jnp.int32)
        idx = jnp.clip(tok, 0, self.vocab - 1)
        return jnp.zeros((self.vocab,), jnp.int32).at[idx.ravel()].add(
            self._weights(owned, valid).ravel())

    def finalize(self, total, sd: ShuffledData):
        return np.asarray(total, np.int64)

    def flops(self, sd: ShuffledData):
        return sd.owned_cells * 4.0

    def combiner(self):
        return TokenCountCombiner(self.vocab)


def token_histogram_job(vocab: int, *, n_partitions: int = 8,
                        codec="identity", tile: int = 256) -> MapReduceJob:
    """Wordcount as a composable job. ``codec="int16"`` halves shuffle bytes
    losslessly for ``vocab < 32767`` (see module docstring)."""
    if codec == "int16":
        codec = Int16Codec(max_abs=float(vocab))
    return MapReduceJob("token_histogram", HashPartitioner(n_partitions),
                        TokenHistogramReducer(vocab), codec=codec, tile=tile)


def token_histogram(tokens: np.ndarray, vocab: int, *, n_partitions: int = 8,
                    codec="identity", tile: int = 256,
                    mesh=None, engine: str = "auto") -> JobResult:
    """Count token occurrences across any token source block (e.g.
    ``SyntheticTokens.block`` / ``Pipeline.batch_at``). -> JobResult whose
    output is a [vocab] int64 count vector."""
    items = np.asarray(tokens).reshape(-1).astype(np.float32)
    job = token_histogram_job(vocab, n_partitions=n_partitions, codec=codec,
                              tile=tile)
    return run_job(job, items, mesh=mesh, engine=engine)
