"""Composable MapReduce on a jax mesh: a four-stage streaming pipeline.

Stage plugins (``Partitioner`` / ``Combiner`` / ``ShuffleCodec`` /
``Reducer``) compose into a ``MapReduceJob`` executed by the split-streaming
executor (``executor.py``): a ``SplitSource`` feeds HDFS-block-analog
catalog splits through map -> combine -> shuffle -> reduce, with a prefetch
thread double-buffering the next split's fetch + host->device transfer under
the current split's compute. Monoid reducers (wordcount) get Hadoop-style
map-side combine — only combined accumulators persist across splits, so
catalogs larger than device memory stream at full speed; cross-row reducers
(pair counting) accumulate wire-dtype shuffle streams and reduce once at the
end. ``run_job``/``run_jobs`` are the one-split special case of the same
code path.

Two engines run each split (``job.py``): ``device`` (the default —
wire-dtype shuffle, capacity tiers, masked batched reduce; under a
``data``-axis mesh the tiers shard across the axis and tier partials combine
with a psum) and ``host`` (the numpy + ``lax.map`` oracle, bit-identical for
exact codecs on or off mesh, streaming or monolithic). Every run emits
``StageStats`` for per-stage Amdahl accounting, including the
exposed-vs-hidden split I/O decomposition (``fetch_wall_s`` /
``overlap_hidden_s``). The paper's two apps (``zones.py``, ``stats.py``) and
the wordcount job (``wordcount.py``) are thin definitions on this API;
``api.py`` keeps the legacy surface.
"""
# Job API (the composable surface)
from repro.mapreduce.codecs import (EncodedShuffle, IdentityCodec,
                                    Int8BlockCodec, Int16Codec, ShuffleCodec,
                                    available_codecs, get_codec,
                                    register_codec)
from repro.mapreduce.instrumentation import (RequestStats, StageStats,
                                             latency_summary)
from repro.mapreduce.job import (DeviceShuffledData, HashPartitioner,
                                 JobResult, MappedSplit, MapReduceJob,
                                 Partitioner, Reducer, ResidentCatalog,
                                 ShuffledData, StreamSummary, TierData,
                                 concat_mapped, group_batch_compatible,
                                 map_split_device, plan_tiers, reduce_stage,
                                 run_job, run_jobs, shuffle_once,
                                 shuffle_reduce_device,
                                 shuffle_reduce_device_streamed,
                                 shuffle_signature, shuffle_stage)
from repro.mapreduce.executor import (Combiner, JobDeadlineExceeded,
                                      LaneCancelled, LanePool,
                                      run_job_streaming, run_jobs_streaming)
from repro.mapreduce.spill import (SpillConfig, SpilledChunk, SpillStore,
                                   mapped_to_host, mapped_wire_nbytes,
                                   plan_bounds)
from repro.mapreduce.zones import (PairCountReducer, ZonePartitioner,
                                   neighbor_pairs_dense, neighbor_search_job)
from repro.mapreduce.stats import PairHistReducer, neighbor_statistics_job
from repro.mapreduce.wordcount import (TokenCountCombiner,
                                       TokenHistogramReducer, token_histogram,
                                       token_histogram_job)

# Legacy surface (deprecated wrappers; kept for compatibility)
from repro.mapreduce.api import ZonedData, bucket_by_zone, sharded_zone_reduce
from repro.mapreduce.zones import neighbor_search_count
from repro.mapreduce.stats import neighbor_statistics
