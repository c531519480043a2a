"""Split-streaming MapReduce executor: the four-stage pipeline
map -> combine -> shuffle -> reduce over HDFS-block-analog catalog splits.

The paper's whole premise is streaming — Hadoop moves block-sized splits
through the pipeline and the win on low-power nodes comes from keeping
sequential I/O flowing while shrinking CPU cost per byte. This module makes
the engine that shape: a ``SplitSource`` (``data/pipeline.py``) feeds splits
one at a time, each split runs the SAME map/shuffle/reduce stages as the
monolithic path (``run_job(job, xyz)`` is literally the one-split case), and
two things keep memory and wall time bounded:

- **Map-side combine** (Hadoop's Combiner). A pluggable ``Combiner`` merges
  per-split partials on device, so only combined accumulators persist across
  splits: datasets larger than device memory stream at full engine speed.
  The default is derived from the ``Reducer`` (``Reducer.combiner()``) for
  commutative-monoid outputs — wordcount's token histogram pre-aggregates
  each split to (token, count) rows before the shuffle, cutting shuffle wire
  bytes by the split's duplication factor, exactly the paper's
  shrink-bytes-before-the-boundary move. Reducers whose kernels couple rows
  across items (pair counting: a pair can span two splits) have no valid
  combiner; their splits accumulate as wire-dtype ``MappedSplit`` streams
  (Hadoop's shuffle spill — the reduce starts when the last map ends) and
  one global reduce runs at the end. Bit-identical either way for exact
  codecs: bucket contents are the same multisets and partition reductions
  are commutative integer sums.

- **Transfer/compute overlap** (double buffering). A ``Prefetcher`` thread
  fetches, pre-combines, and ``jax.device_put``s split k+1 while split k is
  still being encoded/reduced on the main thread. ``StageStats`` splits the
  I/O into ``fetch_wall_s`` (exposed — the executor actually waited) and
  ``overlap_hidden_s`` (hidden under compute), plus a per-split record
  stream for straggler analysis (``ft/stragglers.py``).

``mesh=`` composes with streaming: under a ``data`` axis each chip maps its
own block of every split, each split (or the accumulated stream) crosses
the chips in one all-to-all and reduces through the psum-sharded tier path,
and the cross-split combine operates on the replicated partial. Spilled
streams and lanes shuffle from one chip's stream.

- **Concurrent lanes + fault tolerance** (``n_lanes=``, ``speculate=``,
  ``max_retries=``, ``deadline_s=``, ``chaos=``). A ``LanePool`` dispatches
  independent splits to concurrent worker lanes (pinned one-per-device when
  several jax devices and no mesh are present) with Hadoop's reliability
  semantics made real: ``SpeculativePolicy`` verdicts clone the slow split
  onto a free lane and the first finisher commits (loser cancelled,
  buffers reclaimed, bit-identical by the same multiset/commutative-sum
  contracts), transient split failures retry with bounded backoff, a dead
  or wedged lane requeues its split on the survivors through the
  ``ft.Coordinator`` liveness machine, and ``deadline_s`` bounds the job.

    src = MemmapCatalogSplits("catalog.f32", d=3, rows_per_split=1 << 20)
    res = run_job_streaming(neighbor_search_job(0.02, codec="int16"), src)
    res.stats.overlap_fraction, res.stats.n_splits
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import queue
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import Prefetcher, SplitSource  # noqa: F401
from repro.ft.chaos import CancelledFetch, LaneDeath, TransientSplitError
from repro.ft.coordinator import Coordinator, CoordinatorConfig
from repro.ft.stragglers import SpeculativePolicy
from repro.mapreduce.codecs import get_codec
from repro.mapreduce.instrumentation import StageStats
from repro.mapreduce.job import (JobResult, MappedSplit,  # noqa: F401
                                 StreamSummary, concat_mapped,
                                 concat_streams, host_shuffle_reduce,
                                 map_split_device, map_stream,
                                 shuffle_reduce_device,
                                 shuffle_reduce_device_streamed,
                                 validate_batch)
from repro.mapreduce.spill import (SpillConfig, SpillStore, mapped_to_host,
                                   mapped_wire_nbytes, plan_bounds)
from repro.obs.energy import get_meter
from repro.obs.trace import get_tracer


# ---------------------------------------------------------------------------
# Combiner: the pluggable map-side combine stage
# ---------------------------------------------------------------------------

class Combiner:
    """Hadoop's map-side combine as a pluggable stage.

    ``precombine`` runs on the raw split BEFORE map/shuffle (inside the
    prefetch thread, so it overlaps compute) and may rewrite the split into
    an equivalent, smaller item stream — that is where shuffle bytes
    actually shrink. ``combine`` merges per-split reduce partials on device;
    the base implementation is the commutative-monoid tree-sum, correct for
    any reducer whose totals add (all the stock reducers' accumulators are
    sums already — that is how partitions combine).

    A combiner is only VALID when reduce(split A + split B) equals
    combine(reduce(A), reduce(B)) — true for per-row folds like token
    counting, false for cross-row kernels like pair counting. The executor
    therefore derives defaults from ``Reducer.combiner()`` (None = no
    combine, accumulate the shuffle instead) rather than guessing.
    """

    name = "sum"

    def precombine(self, items: np.ndarray) -> np.ndarray:
        """Rewrite one raw split into an equivalent item stream (host side,
        runs in the prefetch thread). Default: unchanged."""
        return items

    def combine(self, acc, partials):
        """Merge a new tuple of per-job reduce partials into the running
        accumulator (device pytrees; ``acc`` is None on the first split)."""
        if acc is None:
            return partials
        return jax.tree.map(jnp.add, acc, partials)


class _Agg:
    """Running padded/real cell + partition-count aggregation over splits."""

    def __init__(self):
        self.pair_pad = 0.0
        self.pair_real = 0.0
        self.owned_cells = 0.0
        self.shard_pad = None
        self.shard_real = None
        self.n_owned = None
        self.n_bucket = None

    def add(self, sd, shard_pad, shard_real):
        self.pair_pad += sd.pair_cells
        self.pair_real += sd.real_pair_cells
        self.owned_cells += sd.owned_cells
        no = np.asarray(sd.n_owned, np.int64)
        nb = np.asarray(sd.n_bucket, np.int64)
        if self.shard_pad is None:
            self.shard_pad = np.asarray(shard_pad, np.float64).copy()
            self.shard_real = np.asarray(shard_real, np.float64).copy()
            self.n_owned, self.n_bucket = no.copy(), nb.copy()
        else:
            self.shard_pad += shard_pad
            self.shard_real += shard_real
            self.n_owned += no
            self.n_bucket += nb

    def finish(self, stats: StageStats):
        stats.reduce_padded_ratio = (self.pair_pad / self.pair_real
                                     if self.pair_real else 1.0)
        if self.shard_pad is not None:
            stats.shard_padded_ratio = tuple(
                float(p / max(r, 1.0))
                for p, r in zip(self.shard_pad, self.shard_real))

    def summary(self) -> StreamSummary:
        return StreamSummary(self.n_owned, self.n_bucket,
                             pair_cells=self.pair_pad,
                             owned_cells=self.owned_cells,
                             real_pair_cells=self.pair_real)


def _resolve_combiner(combiner, jobs, codec):
    """None / "auto" / a ``Combiner`` instance -> the combiner to run (or
    None). "auto" derives from the reducers, and only engages when EVERY
    batched job provides one, they agree, and the codec is exact — a lossy
    codec quantizes the combiner's pre-aggregated counts into a different
    wire domain than the raw items, which would break streaming==monolithic
    parity silently. Pass an instance to force."""
    if combiner is None:
        return None
    if isinstance(combiner, Combiner):
        return combiner
    if combiner != "auto":
        raise ValueError(f"combiner must be None, 'auto', or a Combiner "
                         f"instance, got {combiner!r}")
    if not codec.exact:
        return None
    combs = [j.reducer.combiner() for j in jobs]
    if any(c is None for c in combs):
        return None
    if any(c != combs[0] for c in combs[1:]):
        return None
    return combs[0]


# ---------------------------------------------------------------------------
# External shuffle: spill accumulated wire streams to disk, stream back
# ---------------------------------------------------------------------------

def _resolve_spill(spill) -> SpillConfig | None:
    """None -> off; a number -> ``SpillConfig(budget_bytes=number)``; a
    ``SpillConfig`` -> itself. A config whose budget is None/inf resolves
    to None — never spill, bit-identical to today's accumulate path."""
    if spill is None:
        return None
    cfg = (spill if isinstance(spill, SpillConfig)
           else SpillConfig(budget_bytes=float(spill)))
    return cfg if cfg.enabled else None


class _ResidentMeter:
    """Thread-safe high-water meter of the spill tier's resident wire bytes
    (host-ified pending streams + in-flight writes + read-back ranges) —
    what the acceptance bound ``peak <= budget + one chunk`` measures."""

    def __init__(self):
        self._lock = threading.Lock()
        self.cur = 0
        self.peak = 0

    def add(self, n: int):
        with self._lock:
            self.cur += int(n)
            if self.cur > self.peak:
                self.peak = self.cur

    def sub(self, n: int):
        with self._lock:
            self.cur -= int(n)


def _auto_ranges(cfg: SpillConfig, est_total_bytes: float, P: int) -> int:
    """Read-back range count: ~4 ranges per budget's worth of estimated
    spill, so one range's resident bytes sit well inside the budget; an int
    ``n_ranges`` forces it."""
    if cfg.n_ranges is not None:
        z = int(cfg.n_ranges)
    else:
        z = int(np.ceil(4.0 * float(est_total_bytes)
                        / max(float(cfg.budget_bytes), 1.0)))
    return max(1, min(z, int(P), int(cfg.max_ranges)))


def _range_record_nbytes(rec: dict) -> int:
    n = sum(int(p.nbytes) for p in rec["payloads"])
    n += (int(rec["keys"].nbytes) + int(rec["dest_eff"].nbytes)
          + int(rec["src"].nbytes))
    if rec["skey"] is not None:
        n += int(rec["skey"].nbytes)
    return n


def _streamed_reduce(store: SpillStore, meter: _ResidentMeter, jobs, P: int,
                     stats: StageStats, mesh):
    """Stream every committed partition range back through a ``Prefetcher``
    double buffer — read + host->device transfer of range z+1 hidden under
    range z's shuffle+reduce — into ``shuffle_reduce_device_streamed``.
    Exposed read waits land in ``spill_wall_s``; hidden prefetch time in
    ``overlap_hidden_s``. Each range's wire bytes leave the meter as soon
    as its reduce returns, so peak residency is O(one range)."""

    def produce(z):
        with get_tracer().span("spill-read", cat="io", range=z):
            rec = store.read_range(z)
        nb = _range_record_nbytes(rec)
        meter.add(nb)
        m = MappedSplit(
            payloads=tuple(jnp.asarray(p) for p in rec["payloads"]),
            keys=jnp.asarray(rec["keys"]),
            dest_eff=jnp.asarray(rec["dest_eff"]),
            src=jnp.asarray(rec["src"]),
            skey=(None if rec["skey"] is None
                  else jnp.asarray(rec["skey"])),
            n_rows=int(rec["n_rows"]), d=int(rec["d"]), nbytes_in=0)
        return rec["lo"], rec["hi"], m, nb

    def ranges():
        with Prefetcher(produce, depth=1, n=store.n_ranges) as pf:
            while (got := pf.get()) is not None:
                _, (lo, hi, m, nb), wait, prep = got
                stats.spill_wall_s += wait
                stats.overlap_hidden_s += max(prep - wait, 0.0)
                yield lo, hi, m
                meter.sub(nb)

    return shuffle_reduce_device_streamed(jobs, ranges(), P, stats, mesh)


class _SpillRuntime:
    """Sequential-path spill driver for device accumulate mode.

    Double-buffered in the Hadoop ``io.sort.mb`` spirit: mapped splits
    host-ify into a pending buffer; when it crosses HALF the budget it is
    handed to the store's async writer (one buffer filling while one
    drains) with at most one chunk in flight, so resident wire bytes stay
    bounded by the budget plus one chunk. A chunk bigger than half the
    budget is written synchronously instead of overlapped — tiny budgets
    degrade gracefully to spill-every-split, budget=0 included. If the run
    finishes without ever crossing the threshold, ``finish`` falls back to
    the monolithic concat+reduce verbatim (enabling spill with a roomy
    budget costs only the host-ify copies)."""

    def __init__(self, cfg: SpillConfig, P: int, K: int, stats: StageStats):
        self.cfg = cfg
        self.P = int(P)
        self.K = int(K)
        self.stats = stats
        self.budget = float(cfg.budget_bytes)
        self.meter = _ResidentMeter()
        self.pending: list = []
        self.pending_bytes = 0
        self.splits_seen = 0
        self.n_submitted = 0
        self.exposed_wait_s = 0.0
        self.store: SpillStore | None = None
        self._inflight = collections.deque()   # wire bytes per async chunk

    def _ensure_store(self) -> SpillStore:
        if self.store is None:
            root = self.cfg.dir or tempfile.mkdtemp(prefix="mr-spill-")
            self.store = SpillStore(root, self.P,
                                    write_fault=self.cfg.write_fault,
                                    on_written=self._on_written)
        return self.store

    def _on_written(self, chunk):
        # writer thread: the chunk's host buffers are on disk and dropped
        if self._inflight:
            self.meter.sub(self._inflight.popleft())

    def add(self, m: MappedSplit):
        """Host-ify one mapped split (its device buffers die with the
        caller's reference) and spill when the pending buffer fills."""
        t0 = time.perf_counter()
        h = mapped_to_host(m)
        self.stats.spill_wall_s += time.perf_counter() - t0
        nb = mapped_wire_nbytes(h)
        if self.pending and self.pending_bytes + nb > self.budget / 2:
            self._flush()                  # keep the filling buffer bounded
        self.meter.add(nb)
        self.pending.append(h)
        self.pending_bytes += nb
        self.splits_seen += 1
        if self.pending_bytes > self.budget / 2:
            self._flush()

    def _flush(self):
        if not self.pending:
            return
        store = self._ensure_store()
        if store._bounds is None:
            # first flush plans the range bounds: weight partitions by this
            # chunk's bucket counts, extrapolate total spill from the
            # splits seen so far
            w = np.zeros(self.P, np.float64)
            for h in self.pending:
                w += np.bincount(h.dest_eff, minlength=self.P + 1)[:self.P]
            est = self.pending_bytes * self.K / max(self.splits_seen, 1)
            store.set_bounds(plan_bounds(
                w, _auto_ranges(self.cfg, est, self.P)))
        t0 = time.perf_counter()
        store.wait_writes()                    # <= 1 chunk in flight
        chunk_bytes = self.pending_bytes
        self._inflight.append(chunk_bytes)
        store.submit_chunk(self.pending)
        self.n_submitted += 1
        self.stats.spilled_splits += len(self.pending)
        self.pending = []
        self.pending_bytes = 0
        if chunk_bytes > self.budget / 2:
            store.wait_writes()                # no room to overlap: go sync
        self.exposed_wait_s += time.perf_counter() - t0

    def finish(self, jobs, stats: StageStats, mesh):
        """Final reduce: streamed per-range read-back when anything
        spilled, else the monolithic concat path over the (host) pending
        streams. Same return shape as ``shuffle_reduce_device``."""
        if self.n_submitted == 0:
            stats.spill_peak_bytes = self.meter.peak
            return shuffle_reduce_device(jobs, concat_mapped(self.pending),
                                         self.P, stats, mesh)
        self._flush()                          # remainder chunk
        store = self.store
        t0 = time.perf_counter()
        store.wait_writes()
        self.exposed_wait_s += time.perf_counter() - t0
        store.sweep_staged()
        stats.spill_ranges = store.n_ranges
        out = _streamed_reduce(store, self.meter, jobs, self.P, stats, mesh)
        stats.spill_bytes += store.bytes_written
        stats.spill_chunk_bytes = store.max_chunk_bytes
        stats.spill_peak_bytes = self.meter.peak
        stats.spill_wall_s += self.exposed_wait_s
        stats.overlap_hidden_s += max(
            store.write_wall_s - self.exposed_wait_s, 0.0)
        return out

    def close(self):
        if self.store is not None:
            self.store.close()


# ---------------------------------------------------------------------------
# LanePool: concurrent split lanes + executed speculative re-execution
# ---------------------------------------------------------------------------

class LaneCancelled(Exception):
    """Internal control flow: a losing attempt noticed its cancel event
    between stages and unwound; its partial buffers are dropped."""


class JobDeadlineExceeded(TimeoutError):
    """The per-job ``deadline_s`` elapsed before every split committed."""


#: exceptions a lane treats as transient — re-dispatched with bounded
#: backoff up to ``max_retries`` (Hadoop's per-task retry budget)
RETRYABLE = (TransientSplitError, OSError)


@dataclasses.dataclass
class _LaneTask:
    """One dispatchable unit: run ``fn(cancel_event)`` for split ``key``."""
    key: int
    fn: object
    attempt: int = 0
    clone: bool = False


@dataclasses.dataclass
class _Lane:
    """One worker lane: a thread, optionally pinned to a device."""
    id: int
    thread: threading.Thread | None = None
    alive: bool = True
    declared_dead: bool = False     # liveness machine gave up on it
    last_beat: float = 0.0
    n_tasks: int = 0
    busy_s: float = 0.0
    dead_reason: str = ""


class LanePool:
    """Concurrent split lanes with first-finisher-wins speculative cloning —
    the scheduler that turns ``ft.SpeculativePolicy`` from advisory into
    executed (Hadoop's speculative task re-execution, for real).

    ``n_lanes`` worker threads pull ``_LaneTask``s off one priority queue
    (clones outrank fresh work — a speculation that queues behind the
    backlog can never win). Per key, the FIRST attempt to finish commits —
    its payload lands in ``results`` and the pool's ``on_commit`` hook runs
    under the lock — and every other in-flight attempt for that key is
    cancelled via its ``threading.Event`` (task fns poll it between stages;
    chaos-injected stalls poll it mid-sleep), so the loser unwinds and its
    buffers die with the frame. Commutative merge contracts make the result
    bit-identical whichever attempt wins.

    Failure ladder, per task:

    - ``RETRYABLE`` (transient fetch errors): re-dispatched with bounded
      exponential backoff, up to ``max_retries``; the budget's last failure
      becomes the run's fatal error.
    - ``LaneDeath``: the lane marks itself dead, requeues the task onto the
      surviving lanes at clone priority, and its thread exits — the pool
      *shrinks* instead of hanging.
    - anything else: fatal; ``drain`` raises it.

    ``drain`` is the control loop (runs on the caller's thread): it feeds
    lane heartbeats into an ``ft.Coordinator`` — the SAME heartbeat ->
    degraded -> remesh state machine the training launcher uses — and
    executes its verdicts (remesh = declare stuck lanes dead, cancel and
    requeue their work; abort = every lane is gone), enforces the per-job
    ``deadline_s``, and drives the speculation policy: per tick it reports
    ``running(split, elapsed)`` for in-flight splits and executes
    ``propose()``'s verdict by cloning the slow split onto a free lane.

    Context manager: exit joins every lane thread and (on a clean exit)
    raises if any survived the join — the no-leaked-threads guarantee that
    pairs with ``Prefetcher.stop``'s stuck-fetch error.
    """

    def __init__(self, n_lanes: int, *, policy: SpeculativePolicy | None = None,
                 chaos=None, max_retries: int = 2, backoff_s: float = 0.02,
                 deadline_s: float | None = None, devices=None,
                 liveness_cfg: CoordinatorConfig | None = None,
                 stuck_after_s: float | None = None, on_commit=None,
                 join_timeout_s: float = 30.0, name: str = "lane"):
        assert n_lanes >= 1
        self.n_lanes = int(n_lanes)
        self.policy = policy
        self.chaos = chaos
        self.max_retries = int(max_retries)
        self.backoff_s = float(backoff_s)
        self.deadline_s = deadline_s
        self.devices = list(devices) if devices else None
        self.stuck_after_s = stuck_after_s
        self.on_commit = on_commit
        self.join_timeout_s = float(join_timeout_s)
        self._clock = time.perf_counter
        self._q: queue.PriorityQueue = queue.PriorityQueue()
        self._seq = itertools.count()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._fatal: BaseException | None = None
        self._inflight: dict[int, dict] = {}        # id(task) -> record
        self._by_key: dict[int, list] = {}
        self.submitted: set[int] = set()
        self.results: dict[int, object] = {}
        self.meta: dict[int, dict] = {}             # key -> winning attempt info
        self.retries = 0
        self.speculated = 0
        self.clone_wins = 0
        self.cancelled = 0
        self.dup_drops = 0
        self.lane_deaths = 0
        self.remeshes: list[dict] = []
        self.liveness = Coordinator(
            list(range(self.n_lanes)),
            liveness_cfg or CoordinatorConfig(heartbeat_timeout=0.05,
                                              misses_to_degrade=2,
                                              misses_to_dead=4, min_hosts=1))
        now = self._clock()
        self.lanes = [_Lane(i, last_beat=now) for i in range(self.n_lanes)]
        for lane in self.lanes:
            lane.thread = threading.Thread(
                target=self._worker, args=(lane,),
                name=f"{name}-{lane.id}", daemon=True)
            lane.thread.start()

    # -- submission / results ------------------------------------------------

    @property
    def width(self) -> int:
        """Lanes still alive (the pool shrinks on lane death)."""
        return sum(lane.alive for lane in self.lanes)

    def submit(self, key: int, fn, *, clone: bool = False):
        with self._lock:
            self._submit_locked(_LaneTask(int(key), fn, clone=clone))

    def _submit_locked(self, task: _LaneTask):
        self.submitted.add(task.key)
        # clones and re-dispatches jump the queue: priority 0 beats 1
        self._q.put((0 if (task.clone or task.attempt) else 1,
                     next(self._seq), task))

    # -- the worker lanes ----------------------------------------------------

    def _lane_ctx(self, lane: _Lane):
        """Per-device lanes: pin this lane's computations (and implicit
        ``device_put`` targets) to its own device when a device list was
        given — concurrent splits then run on distinct devices, the
        mesh-as-lanes execution model."""
        if self.devices:
            return jax.default_device(self.devices[lane.id % len(self.devices)])
        return contextlib.nullcontext()

    def _worker(self, lane: _Lane):
        with self._lane_ctx(lane):
            while not self._stop.is_set():
                try:
                    _, _, task = self._q.get(timeout=0.01)
                except queue.Empty:
                    lane.last_beat = self._clock()
                    continue
                with self._lock:
                    if task.key in self.results or self._fatal is not None:
                        continue            # stale: this split already won
                    cancel = threading.Event()
                    rec = {"task": task, "lane": lane.id,
                           "t0": self._clock(), "cancel": cancel}
                    self._inflight[id(task)] = rec
                    self._by_key.setdefault(task.key, []).append(rec)
                lane.n_tasks += 1
                t0 = self._clock()
                requeue = None
                dead = False
                tr = get_tracer()
                try:
                    # the lane-exec span closes in its finally even when the
                    # task dies mid-stage (chaos kill, cancel, transient
                    # fault) — the exception then continues into the ladder
                    # below with every opened span closed
                    with tr.ids(lane=lane.id, split=task.key), \
                         tr.span("lane-exec", cat="lane", lane=lane.id,
                                 split=task.key, attempt=task.attempt,
                                 clone=task.clone):
                        if self.chaos is not None:
                            self.chaos.on_task_start(lane.id, task.key,
                                                     task.attempt, cancel)
                        out = task.fn(cancel)
                except (LaneCancelled, CancelledFetch):
                    with self._lock:
                        self.cancelled += 1
                except LaneDeath as e:
                    with self._lock:
                        lane.alive = False
                        lane.dead_reason = str(e)
                        self.lane_deaths += 1
                        # the dying lane's split must not be lost: requeue a
                        # fresh copy onto the survivors at clone priority
                        self._submit_locked(dataclasses.replace(task))
                    dead = True
                except RETRYABLE as e:
                    if task.attempt >= self.max_retries:
                        with self._lock:
                            if self._fatal is None:
                                self._fatal = e
                    else:
                        requeue = dataclasses.replace(task,
                                                      attempt=task.attempt + 1)
                except BaseException as e:
                    with self._lock:
                        if self._fatal is None:
                            self._fatal = e
                else:
                    self._commit(task, out, self._clock() - t0, lane)
                finally:
                    with self._lock:
                        self._inflight.pop(id(task), None)
                        self._by_key.get(task.key, [])[:] = [
                            r for r in self._by_key.get(task.key, ())
                            if r["task"] is not task]
                    lane.busy_s += self._clock() - t0
                    lane.last_beat = self._clock()
                if dead:
                    return
                if requeue is not None:
                    # bounded exponential backoff, interruptible on shutdown
                    with tr.span("retry", cat="lane", lane=lane.id,
                                 split=task.key, attempt=requeue.attempt):
                        self._stop.wait(self.backoff_s * (2 ** task.attempt))
                    with self._lock:
                        self.retries += 1
                        self._submit_locked(requeue)

    def _commit(self, task: _LaneTask, out, wall_s: float, lane: _Lane):
        with self._lock:
            if task.key in self.results:
                self.dup_drops += 1     # lost the race; buffers die here
                return
            meta = {"lane": lane.id, "attempt": task.attempt,
                    "clone": task.clone, "wall_s": wall_s}
            self.results[task.key] = out
            self.meta[task.key] = meta
            if task.clone:
                self.clone_wins += 1
                get_tracer().instant("clone-win", cat="lane",
                                     split=task.key, lane=lane.id)
            for rec in self._by_key.get(task.key, ()):
                if rec["task"] is not task:
                    rec["cancel"].set()         # losers: unwind between stages
            if self.policy is not None:
                self.policy.finished(task.key, wall_s)
            if self.on_commit is not None:
                self.on_commit(task.key, out, meta)

    # -- the control loop: liveness, deadline, speculation -------------------

    def drain(self, keys=None, *, make_task_fn=None, tick_s: float = 0.002):
        """Block until every key has committed (default: everything
        submitted). Runs the lane-liveness state machine, the per-job
        deadline, and the speculation policy; raises the first fatal error,
        ``JobDeadlineExceeded``, or abort (all lanes dead)."""
        t_start = self._clock()
        while True:
            with self._lock:
                want = set(self.submitted if keys is None else keys)
                fatal = self._fatal
                done = want <= self.results.keys()
            if fatal is not None:
                raise fatal
            if done:
                return
            now = self._clock()
            if (self.deadline_s is not None
                    and now - t_start > self.deadline_s):
                missing = sorted(want - set(self.results))
                raise JobDeadlineExceeded(
                    f"job deadline {self.deadline_s}s exceeded with splits "
                    f"{missing} uncommitted ({self.width}/{self.n_lanes} "
                    f"lanes alive)")
            self._liveness_tick(now)
            self._speculate(now, make_task_fn)
            time.sleep(tick_s)

    def _liveness_tick(self, now: float):
        coord = self.liveness
        with self._lock:
            for lane in self.lanes:
                beating = lane.alive and (
                    self.stuck_after_s is None
                    or now - lane.last_beat <= self.stuck_after_s)
                if beating:
                    coord.heartbeat(lane.id, now)
            act = coord.tick(now)
            if act["action"] == "remesh":
                for lid in act["dead"]:
                    lane = self.lanes[lid]
                    lane.declared_dead = True
                    if lane.alive:
                        # stuck, not self-reported: give up on it — cancel
                        # its in-flight work and requeue fresh copies
                        lane.alive = False
                        lane.dead_reason = (lane.dead_reason
                                            or "no heartbeat (stuck)")
                        for rec in list(self._inflight.values()):
                            if rec["lane"] == lid:
                                rec["cancel"].set()
                                self._submit_locked(
                                    dataclasses.replace(rec["task"]))
                self.remeshes.append(act)
                coord.remesh_done()
            elif act["action"] == "abort":
                if self._fatal is None:
                    self._fatal = RuntimeError(
                        "every lane is dead: "
                        + "; ".join(f"lane {ln.id}: {ln.dead_reason}"
                                    for ln in self.lanes if not ln.alive))

    def _speculate(self, now: float, make_task_fn):
        if self.policy is None:
            return
        with self._lock:
            earliest: dict[int, float] = {}
            for rec in self._inflight.values():
                k = rec["task"].key
                earliest[k] = min(earliest.get(k, rec["t0"]), rec["t0"])
            for k, t0 in earliest.items():
                if k not in self.results:
                    self.policy.running(k, now - t0)
            verdict = self.policy.propose()
            if verdict["action"] == "speculate" and make_task_fn is not None:
                k = verdict["split"]
                self.speculated += 1
                get_tracer().instant("clone-race", cat="lane", split=k)
                self._submit_locked(_LaneTask(k, make_task_fn(k), clone=True))

    # -- shutdown ------------------------------------------------------------

    def shutdown(self, *, check: bool = True):
        self._stop.set()
        with self._lock:
            for rec in self._inflight.values():
                rec["cancel"].set()
        leaked = []
        for lane in self.lanes:
            if lane.thread is not None:
                lane.thread.join(timeout=self.join_timeout_s)
                if lane.thread.is_alive():
                    leaked.append(lane.id)
        if leaked and check:
            raise RuntimeError(
                f"LanePool shutdown leaked lane thread(s) {leaked}: still "
                f"running {self.join_timeout_s}s after stop — a task is "
                f"ignoring its cancel event")

    def __enter__(self) -> "LanePool":
        return self

    def __exit__(self, exc_type, exc, tb):
        # on the error path, still stop + join but don't let a leak report
        # mask the original failure
        self.shutdown(check=exc_type is None)


# ---------------------------------------------------------------------------
# The streaming executor
# ---------------------------------------------------------------------------

def _resolve_policy(speculate) -> SpeculativePolicy | None:
    """None/False -> off; True -> default policy; a ``SpeculativeConfig``
    or ``SpeculativePolicy`` -> that policy."""
    if not speculate:
        return None
    if isinstance(speculate, SpeculativePolicy):
        return speculate
    if speculate is True:
        return SpeculativePolicy()
    return SpeculativePolicy(speculate)      # a SpeculativeConfig


def run_jobs_streaming(jobs, source: SplitSource, *, mesh=None,
                       engine: str = "auto", combiner="auto",
                       prefetch: int = 2, straggler_monitor=None,
                       n_lanes: int = 1, speculate=None, chaos=None,
                       max_retries: int = 0, retry_backoff_s: float = 0.05,
                       deadline_s: float | None = None,
                       spill=None) -> list[JobResult]:
    """Stream every split of ``source`` through map -> combine -> shuffle ->
    reduce and return one ``JobResult`` per job (all sharing one
    ``StageStats`` with per-split records).

    - ``combiner="auto"`` derives the map-side combine from the reducers
      (see ``_resolve_combiner``); ``None`` disables it (splits accumulate
      as wire-dtype streams, one global reduce at the end); a ``Combiner``
      instance forces it.
    - ``prefetch`` is the double-buffer depth: >0 fetches + device-transfers
      split k+1 on a background thread while split k computes
      (``overlap_hidden_s`` records what that hid); 0 runs synchronously
      (what ``run_jobs`` uses for its one-split delegate).
    - ``straggler_monitor`` (``ft.StragglerMonitor``) receives
      ``record(split_index, split_wall_s)`` per split, so slow splits can
      drive Hadoop-style speculative re-execution policy
      (``ft.SpeculativePolicy``).
    - ``mesh`` composes: per-split (or final) reduces run psum-sharded over
      the ``data`` axis; cross-split combine sees the replicated partial.

    Lane execution (any of the following engages the ``LanePool`` path;
    the default is the sequential prefetched pipeline above):

    - ``n_lanes > 1``: splits dispatch concurrently over worker lanes —
      pinned one-per-device when several devices exist and no ``mesh`` is
      given (the mesh-as-lanes model: different splits on different
      devices, Hadoop's actual parallelism), else concurrent dispatch
      streams on one device.
    - ``speculate``: True / ``SpeculativeConfig`` / ``SpeculativePolicy`` —
      the policy's verdicts are EXECUTED: a slow split is cloned onto a
      free lane, first finisher wins, the loser is cancelled between
      stages. Bit-identical results either way (commutative merges).
    - ``chaos`` (``ft.LaneChaos``): injected lane deaths/delays; a dead
      lane's work requeues onto the survivors and the pool shrinks.
    - ``max_retries`` / ``retry_backoff_s``: per-split transient-fault
      retry budget with bounded exponential backoff.
    - ``deadline_s``: per-job deadline — ``JobDeadlineExceeded`` instead of
      a hang when splits cannot finish.

    ``spill`` (a byte budget or a ``SpillConfig``) engages the external
    shuffle tier for device-engine accumulate mode (no valid combiner):
    when the accumulated wire streams exceed the budget they spill to
    partition-range-bucketed segment files and the final reduce streams
    each range back through a prefetch double buffer — peak resident wire
    bytes O(spill chunk) instead of O(catalog/codec ratio), bit-identical
    for any budget (0 = spill everything, None/inf = never spill ≡ off).
    When a combiner is active nothing accumulates, so ``spill`` is a
    no-op; the host engine rejects it. With lanes, every split's stream
    spills at map time (segments commit with the split, so retried/cloned
    splits stay lane-safe). Spill files live under ``SpillConfig.dir`` (a
    fresh temp dir by default) and are reclaimed on exit, success or
    failure.

    The partition space must be split-independent (``n_partitions`` is read
    from the first split) — true for the stock zone/hash partitioners.
    """
    if not jobs:
        return []
    validate_batch(jobs)
    if engine == "auto":
        engine = "device"
    if engine not in ("device", "host"):
        raise ValueError(f"unknown engine {engine!r}; "
                         "expected 'auto', 'device', or 'host'")
    j0 = jobs[0]
    codec = get_codec(j0.codec)
    part = j0.partitioner
    comb = _resolve_combiner(combiner, jobs, codec)
    K = int(source.n_splits())
    device = engine == "device"
    spill_cfg = _resolve_spill(spill)
    if spill_cfg is not None and not device:
        raise ValueError("spill= requires the device engine: the spill "
                         "tier stores wire-dtype encoded streams")
    if comb is not None:
        spill_cfg = None     # combine mode never accumulates: nothing to spill
    stats = StageStats(job="+".join(j.name for j in jobs), engine=engine,
                       codec=codec.name, n_splits=K,
                       combiner=comb.name if comb else "")
    policy = _resolve_policy(speculate)
    tr = get_tracer()
    meter = get_meter()
    mtok = meter.begin()
    lanes = (n_lanes > 1 or policy is not None or chaos is not None
             or max_retries > 0 or deadline_s is not None)
    with tr.span("job", cat="job", job=stats.job,
                 mode="lanes" if lanes else "stream"):
        if lanes:
            out = _run_jobs_lanes(
                jobs, source, mesh=mesh, device=device, codec=codec,
                part=part, comb=comb, K=K, stats=stats,
                straggler_monitor=straggler_monitor,
                n_lanes=max(1, int(n_lanes)), policy=policy, chaos=chaos,
                max_retries=max_retries, retry_backoff_s=retry_backoff_s,
                deadline_s=deadline_s, spill_cfg=spill_cfg)
        else:
            out = _run_jobs_stream(
                jobs, source, mesh=mesh, device=device, codec=codec,
                part=part, comb=comb, K=K, stats=stats,
                straggler_monitor=straggler_monitor, prefetch=prefetch,
                spill_cfg=spill_cfg)
    meter.attribute(mtok, stats)
    return out


def _run_jobs_stream(jobs, source, *, mesh, device, codec, part, comb, K,
                     stats, straggler_monitor, prefetch, spill_cfg):
    """The one-lane streaming run: splits map in order (overlapped with
    their fetch by a ``Prefetcher`` when ``prefetch``), then one global
    shuffle + reduce, or a per-split reduce folded by the combiner.
    -> one JobResult per job."""
    tr = get_tracer()

    def fetch(k):
        # -> (items, raw_rows, raw_bytes): the RAW split size is carried
        # alongside so n_items/map_bytes report what was actually fetched,
        # not the combiner's pre-aggregated rewrite
        s = source.split(k)
        raw_rows, raw_bytes = len(s), int(np.asarray(s).nbytes)
        if comb is not None:
            s = comb.precombine(s)
        return s, raw_rows, raw_bytes

    def fetch_to_device(k):
        # runs on the prefetch thread: host I/O, precombine, AND the
        # host->device transfer all overlap the main thread's compute
        with tr.span("fetch", cat="io", split=k):
            s, raw_rows, raw_bytes = fetch(k)
            return (jax.device_put(np.ascontiguousarray(
                np.asarray(s, np.float32))), raw_rows, raw_bytes)

    def synchronous():
        for k in range(K):
            t0 = time.perf_counter()
            item = fetch(k)
            dt = time.perf_counter() - t0
            yield k, item, dt, dt

    acc = None
    mapped = []
    host_items = []
    recs = []
    agg = _Agg()
    raw_items_total = 0
    raw_bytes_total = 0
    P = None
    spill_rt = None

    def consume(k, item, wait_s, prep_s):
        nonlocal acc, P, raw_items_total, raw_bytes_total, spill_rt
        items_k, raw_rows, raw_bytes = item
        raw_items_total += raw_rows
        raw_bytes_total += raw_bytes
        stats.fetch_wall_s += wait_s
        stats.overlap_hidden_s += max(prep_s - wait_s, 0.0)
        if tr.enabled and wait_s > 0:
            # the wait just ended: record the exposed fetch stall span
            # retroactively (the hidden part already traced as "fetch" on
            # the prefetch thread)
            t_now = tr.now()
            tr.record("fetch-wait", t_now - wait_s, t_now, cat="io", split=k)
        if P is None:
            P = int(part.n_partitions(items_k))
        rec = {"split": k, "n_items": raw_rows, "fetch_wait_s": wait_s,
               "fetch_prep_s": prep_s}
        m0, s0, r0 = stats.map_wall_s, stats.shuffle_wall_s, stats.reduce_wall_s
        if device:
            t0 = time.perf_counter()
            # spill stores one stream per split; otherwise each chip of a
            # data mesh maps its own block of the split
            m = (map_split_device(part, codec, items_k, P)
                 if spill_cfg is not None
                 else map_stream(part, codec, items_k, P, mesh))
            stats.map_wall_s += time.perf_counter() - t0
            if comb is None:
                if spill_cfg is not None:
                    if spill_rt is None:
                        spill_rt = _SpillRuntime(spill_cfg, P, K, stats)
                    spill_rt.add(m)      # host-ify + maybe flush to disk
                else:
                    mapped.append(m)
            else:
                totals, sd, sp, sr = shuffle_reduce_device(jobs, m, P, stats,
                                                           mesh)
                agg.add(sd, sp, sr)
                with tr.span("combine", cat="stage", split=k):
                    t0 = time.perf_counter()
                    acc = comb.combine(acc, totals)
                    stats.combine_wall_s += time.perf_counter() - t0
        else:
            items_h = np.asarray(items_k)
            if comb is None:
                host_items.append(items_h)
            else:
                totals, sd, sp, sr = host_shuffle_reduce(jobs, items_h,
                                                         stats, mesh)
                agg.add(sd, sp, sr)
                with tr.span("combine", cat="stage", split=k):
                    t0 = time.perf_counter()
                    acc = comb.combine(acc, totals)
                    stats.combine_wall_s += time.perf_counter() - t0
        rec["map_s"] = stats.map_wall_s - m0
        rec["shuffle_s"] = stats.shuffle_wall_s - s0
        rec["reduce_s"] = stats.reduce_wall_s - r0
        # the split's own end-to-end cost: its fetch/transfer work (prep, as
        # measured in the producer whether or not it was hidden) plus its
        # processing walls. In accumulate mode processing is deferred to the
        # one global reduce, so per-split cost is I/O-dominated — exactly
        # the signal Hadoop's speculative execution watches (a split whose
        # read stalls shows up here even when other splits hid theirs).
        rec["wall_s"] = (prep_s + rec["map_s"] + rec["shuffle_s"]
                         + rec["reduce_s"])
        recs.append(rec)
        if straggler_monitor is not None:
            straggler_monitor.record(k, rec["wall_s"])

    try:
        if K > 1 and prefetch > 0:
            produce = fetch_to_device if device else fetch
            with Prefetcher(produce, depth=prefetch, n=K) as pf:
                while (got := pf.get()) is not None:
                    with tr.ids(split=got[0]):
                        consume(*got)
        else:
            for got in synchronous():
                with tr.ids(split=got[0]):
                    consume(*got)
        assert len(recs) == K, (len(recs), K)

        if comb is None:
            # no valid map-side combine: the accumulated wire-format streams
            # cross ONE global shuffle+reduce (Hadoop's reduce-after-last-map)
            # — streamed per partition range from disk when they spilled
            if device:
                if spill_rt is not None:
                    totals, sd, sp, sr = spill_rt.finish(jobs, stats, mesh)
                else:
                    totals, sd, sp, sr = shuffle_reduce_device(
                        jobs, concat_streams(mapped), P, stats, mesh)
            else:
                items_all = (host_items[0] if len(host_items) == 1
                             else np.concatenate(host_items, axis=0))
                totals, sd, sp, sr = host_shuffle_reduce(jobs, items_all,
                                                         stats, mesh)
            agg.add(sd, sp, sr)
            summary = sd
        else:
            t0 = time.perf_counter()
            totals = jax.block_until_ready(acc)
            stats.combine_wall_s += time.perf_counter() - t0
            summary = agg.summary()
    finally:
        if spill_rt is not None:
            spill_rt.close()         # reclaim segments, success or failure
    agg.finish(stats)
    # n_items/map_bytes always mean the RAW catalog (what the maps read) —
    # the per-split stages counted post-precombine rows when a combiner ran
    stats.n_items = raw_items_total
    stats.map_bytes = raw_bytes_total
    stats.splits = tuple(recs)
    return [JobResult(j.reducer.finalize(t, summary), stats)
            for j, t in zip(jobs, totals)]


def _fence_mapped(m):
    """Block until one ``MappedSplit``'s arrays are materialized, so a lane's
    reported wall covers real device work, not dispatch."""
    jax.block_until_ready([m.payloads, m.keys, m.dest_eff, m.src]
                          + ([m.skey] if m.skey is not None else []))
    return m


def _run_jobs_lanes(jobs, source, *, mesh, device, codec, part, comb, K,
                    stats, straggler_monitor, n_lanes, policy, chaos,
                    max_retries, retry_backoff_s, deadline_s,
                    spill_cfg=None):
    """The ``LanePool`` execution path of ``run_jobs_streaming``: splits run
    concurrently, each lane's stages fill a PRIVATE ``StageStats`` that
    merges into the shared one at commit (under the pool lock, so the
    stage-wall accumulation the sequential path does in-place stays
    race-free), and only the FIRST committed attempt per split contributes —
    a cancelled speculation loser's partial work is dropped with its frame.
    Commit order is nondeterministic; every cross-split merge is commutative
    (integer-sum accumulators / multiset bucket contents), which is exactly
    the contract that makes the results bit-identical to the sequential and
    monolithic paths."""
    t_run0 = time.perf_counter()
    devices = None
    if device and mesh is None:
        devs = jax.devices()
        if len(devs) > 1:
            devices = devs        # per-device lanes (lane i -> device i % D)

    agg = _Agg()
    mapped: dict[int, object] = {}
    host_items: dict[int, np.ndarray] = {}
    recs: list[dict] = []
    state = {"acc": None, "P": None, "raw_items": 0, "raw_bytes": 0}

    # Lane-mode spill: every split's stream is staged to disk by its own
    # lane (no cross-lane accumulation buffer to bound — lanes run
    # concurrently, so the budget degenerates to spill-per-split) and the
    # winning attempt's segments are finalize-renamed in on_commit, under
    # the pool lock. Losing clones leave only staged litter, swept before
    # read-back. The first lane to stage plans the range bounds.
    spill_state = None
    if spill_cfg is not None and device and comb is None:
        spill_state = {"cfg": spill_cfg, "store": None,
                       "meter": _ResidentMeter(),
                       "lock": threading.Lock(),
                       "ready": threading.Event()}

    def spill_store_for(h, P_k):
        st = spill_state
        if not st["ready"].is_set():
            with st["lock"]:
                if not st["ready"].is_set():
                    root = (st["cfg"].dir
                            or tempfile.mkdtemp(prefix="mr-spill-"))
                    store = SpillStore(root, P_k,
                                       write_fault=st["cfg"].write_fault)
                    w = np.bincount(h.dest_eff, minlength=P_k + 1)[:P_k]
                    est = mapped_wire_nbytes(h) * K
                    store.set_bounds(plan_bounds(
                        w, _auto_ranges(st["cfg"], est, P_k)))
                    st["store"] = store
                    st["ready"].set()
        st["ready"].wait()
        return st["store"]

    def fetch(k, cancel):
        if hasattr(source, "split_cancellable"):
            s = source.split_cancellable(k, cancel)
        else:
            s = source.split(k)
        raw_rows, raw_bytes = len(s), int(np.asarray(s).nbytes)
        if comb is not None:
            s = comb.precombine(s)
        return s, raw_rows, raw_bytes

    def make_task(k):
        def fn(cancel):
            tr = get_tracer()
            local = StageStats()
            t0 = time.perf_counter()
            s, raw_rows, raw_bytes = fetch(k, cancel)
            t1 = time.perf_counter()
            local.fetch_wall_s = t1 - t0
            if tr.enabled:
                # lane fetches are synchronous, so the whole fetch is an
                # exposed wait from the lane's point of view
                tr.record("fetch-wait", t0, t1, cat="io", split=k)
            if cancel.is_set():
                raise LaneCancelled(k)
            P_k = int(part.n_partitions(s))
            if device:
                items_k = jax.device_put(np.ascontiguousarray(
                    np.asarray(s, np.float32)))
                t0 = time.perf_counter()
                m = map_split_device(part, codec, items_k, P_k)
                local.map_wall_s += time.perf_counter() - t0
                if cancel.is_set():
                    raise LaneCancelled(k)
                if comb is None:
                    if spill_state is not None:
                        t0 = time.perf_counter()
                        h = mapped_to_host(_fence_mapped(m))
                        del m, items_k       # device buffers reclaimable now
                        nb = mapped_wire_nbytes(h)
                        store = spill_store_for(h, P_k)
                        spill_state["meter"].add(nb)
                        try:
                            if cancel.is_set():
                                raise LaneCancelled(k)
                            chunk = store.stage_chunk([h], store.next_tag())
                        finally:
                            spill_state["meter"].sub(nb)
                        local.spill_wall_s += time.perf_counter() - t0
                        local.spilled_splits = 1
                        payload = ("spilled", chunk, nb)
                    else:
                        payload = ("mapped", _fence_mapped(m))
                else:
                    totals, sd, sp, sr = shuffle_reduce_device(
                        jobs, m, P_k, local, mesh)
                    payload = ("acc", jax.block_until_ready(totals),
                               sd, sp, sr)
            else:
                items_h = np.asarray(s)
                if comb is None:
                    payload = ("items", items_h)
                else:
                    totals, sd, sp, sr = host_shuffle_reduce(
                        jobs, items_h, local, mesh)
                    payload = ("acc", totals, sd, sp, sr)
            if cancel.is_set():
                raise LaneCancelled(k)
            return {"payload": payload, "P": P_k, "raw_rows": raw_rows,
                    "raw_bytes": raw_bytes, "local": local}
        return fn

    def on_commit(k, out, meta):
        # runs under the pool lock: the one winning attempt per split merges
        # its private stats + partials into the shared state, serialized
        local = out["local"]
        stats.merge_from(local)
        state["raw_items"] += out["raw_rows"]
        state["raw_bytes"] += out["raw_bytes"]
        if state["P"] is None:
            state["P"] = out["P"]
        kind, *rest = out["payload"]
        if kind == "acc":
            totals, sd, sp, sr = rest
            agg.add(sd, sp, sr)
            with get_tracer().span("combine", cat="stage", split=k):
                t0 = time.perf_counter()
                state["acc"] = comb.combine(state["acc"], totals)
                stats.combine_wall_s += time.perf_counter() - t0
        elif kind == "spilled":
            # lane-safe commit: the winning attempt's staged segments
            # finalize-rename here, serialized under the pool lock; a
            # losing clone's chunk never reaches this hook
            spill_state["store"].commit_chunk(rest[0])
        elif kind == "mapped":
            mapped[k] = rest[0]
        else:
            host_items[k] = rest[0]
        recs.append({"split": k, "n_items": out["raw_rows"],
                     "fetch_wait_s": local.fetch_wall_s,
                     "fetch_prep_s": local.fetch_wall_s,
                     "map_s": local.map_wall_s,
                     "shuffle_s": local.shuffle_wall_s,
                     "reduce_s": local.reduce_wall_s,
                     "wall_s": meta["wall_s"], "lane": meta["lane"],
                     "attempt": meta["attempt"], "clone": meta["clone"]})
        if straggler_monitor is not None and straggler_monitor is not policy:
            straggler_monitor.record(k, meta["wall_s"])

    try:
        with LanePool(n_lanes, policy=policy, chaos=chaos,
                      max_retries=max_retries, backoff_s=retry_backoff_s,
                      deadline_s=deadline_s, devices=devices,
                      on_commit=on_commit) as pool:
            for k in range(K):
                pool.submit(k, make_task(k))
            pool.drain(range(K), make_task_fn=make_task)
            stats.n_lanes = n_lanes
            stats.speculated = pool.speculated
            stats.clone_wins = pool.clone_wins
            stats.retries = pool.retries
            stats.lane_walls = tuple(round(ln.busy_s, 6)
                                     for ln in pool.lanes)
        assert len(recs) == K, (len(recs), K)

        P = state["P"]
        if comb is None:
            # one global shuffle+reduce over the accumulated per-split
            # streams — streamed back per partition range when they
            # spilled, else concatenated in split order (deterministic
            # regardless of commit order — and bit-identical to any order
            # by the multiset contract)
            if device:
                if spill_state is not None:
                    store = spill_state["store"]
                    store.sweep_staged()     # cancelled clones' litter
                    stats.spill_ranges = store.n_ranges
                    totals, sd, sp, sr = _streamed_reduce(
                        store, spill_state["meter"], jobs, P, stats, mesh)
                    stats.spill_bytes += store.bytes_written
                    stats.spill_chunk_bytes = store.max_chunk_bytes
                    stats.spill_peak_bytes = spill_state["meter"].peak
                else:
                    totals, sd, sp, sr = shuffle_reduce_device(
                        jobs, concat_mapped([mapped[k] for k in range(K)]),
                        P, stats, mesh)
            else:
                hs = [host_items[k] for k in range(K)]
                items_all = (hs[0] if len(hs) == 1
                             else np.concatenate(hs, axis=0))
                totals, sd, sp, sr = host_shuffle_reduce(jobs, items_all,
                                                         stats, mesh)
            agg.add(sd, sp, sr)
            summary = sd
        else:
            t0 = time.perf_counter()
            totals = jax.block_until_ready(state["acc"])
            stats.combine_wall_s += time.perf_counter() - t0
            summary = agg.summary()
    finally:
        if spill_state is not None and spill_state["store"] is not None:
            spill_state["store"].close()
    agg.finish(stats)
    stats.n_items = state["raw_items"]
    stats.map_bytes = state["raw_bytes"]
    stats.splits = tuple(sorted(recs, key=lambda r: r["split"]))
    stats.elapsed_s = time.perf_counter() - t_run0
    return [JobResult(j.reducer.finalize(t, summary), stats)
            for j, t in zip(jobs, totals)]


def run_job_streaming(job, source: SplitSource, *, mesh=None,
                      engine: str = "auto", combiner="auto",
                      prefetch: int = 2, straggler_monitor=None,
                      n_lanes: int = 1, speculate=None, chaos=None,
                      max_retries: int = 0, retry_backoff_s: float = 0.05,
                      deadline_s: float | None = None,
                      spill=None) -> JobResult:
    """Stream one job over a ``SplitSource``. -> JobResult(output, stats)."""
    return run_jobs_streaming([job], source, mesh=mesh, engine=engine,
                              combiner=combiner, prefetch=prefetch,
                              straggler_monitor=straggler_monitor,
                              n_lanes=n_lanes, speculate=speculate,
                              chaos=chaos, max_retries=max_retries,
                              retry_backoff_s=retry_backoff_s,
                              deadline_s=deadline_s, spill=spill)[0]
