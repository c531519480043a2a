"""Composable MapReduce jobs: two engines, pluggable stages.

The paper's wins (buffered writes, LZO shuffle compression, direct I/O) all
swap a *stage* of Hadoop's fixed map -> shuffle -> reduce pipeline without
touching job logic. This module makes that the API:

- ``Partitioner``   (map): key assignment + border-replication policy,
- ``ShuffleCodec``  (shuffle): wire format, by registry name (``codecs.py``),
- ``Reducer``       (reduce): per-partition kernel + host-side finalize,

composed into a ``MapReduceJob`` and executed by one of two engines:

- ``engine="device"`` (default off-mesh): the hot path. Partition
  assignment, border replication, argsort-based bucketing, and capacity
  padding are vectorized array ops; the payload crosses the shuffle in the
  codec's *wire dtype* (int16/int8) and is decoded on-device at the start
  of the reduce, so shuffle traffic shrinks with the codec ratio.
  Partitions are grouped into size tiers (``plan_tiers``) so one skewed
  partition doesn't inflate every partition's capacity padding, and each
  tier reduces through batched masked kernels (``pair_count_masked`` & co.:
  Pallas partition-grid kernels on TPU, the blocked engine
  elsewhere) instead of a sequential ``lax.map``. Under a ``data``-axis
  mesh the job runs as a cluster: each chip maps its own contiguous block
  of the rows (``map_blocks``), one all-to-all moves every owned row and
  border copy to the chip that owns its partition's tier slot
  (``_exchange_jit``), tier partition counts are padded to a multiple of
  the axis size so the tiers are born sharded, each shard reduces its own
  rows, and tier partials combine with a ``psum``
  (``_reduce_tier_sharded``).
- ``engine="host"``: the original numpy shuffle + per-partition ``lax.map``
  reduce. Kept as the oracle-parity path (also under a mesh: the device
  engine's sharded results are bit-identical for exact codecs).

Both engines handle multi-job batching (jobs sharing a partitioner/codec do
ONE map+shuffle and a single fused reduce pass) and emit ``StageStats`` —
per-stage bytes, FLOPs, and wall time (fenced with ``block_until_ready``) —
which ``StageStats.roofline()`` turns into the paper's Amdahl-number
analysis for *any* job, not just the two hard-coded apps.

    job = MapReduceJob("search", ZonePartitioner(radius), PairCountReducer(r),
                       codec="int16")
    result = run_job(job, xyz)                     # device engine
    result = run_job(job, xyz, mesh=mesh)          # device engine, sharded
    result.output, result.stats.to_dict()
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.compat import shard_map as _shard_map_compat
from repro.mapreduce.codecs import ShuffleCodec, get_codec
from repro.mapreduce.instrumentation import StageStats
from repro.obs.energy import get_meter
from repro.obs.trace import get_tracer, recording


def _round_up(x: int, m: int) -> int:
    return max(m, ((x + m - 1) // m) * m)


def _pad_rows(x: np.ndarray, n: int, fill: float) -> np.ndarray:
    out = np.full((n, x.shape[1]), fill, x.dtype)
    out[:len(x)] = x
    return out


def _data_axis_size(mesh) -> int:
    if mesh is None or "data" not in mesh.axis_names:
        return 1
    return int(mesh.shape["data"])


def _data_devices(mesh) -> list:
    """Entry ``c``: the devices that hold shard ``c`` of the ``data`` axis
    (more than one where other mesh axes replicate it)."""
    devs = np.moveaxis(np.asarray(mesh.devices),
                       mesh.axis_names.index("data"), 0)
    return [list(row) for row in devs.reshape(devs.shape[0], -1)]


# ---------------------------------------------------------------------------
# Pluggable stages
# ---------------------------------------------------------------------------

class Partitioner:
    """Map stage: assigns each item a partition key, and optionally replicates
    items into neighboring partitions (the paper's mappers "copy objects
    within a certain region around each block")."""

    def n_partitions(self, items: np.ndarray) -> int:
        raise NotImplementedError

    def assign(self, items: np.ndarray) -> np.ndarray:
        """-> [n] int32 owning-partition ids."""
        raise NotImplementedError

    def replicas(self, items: np.ndarray, keys: np.ndarray, n_parts: int):
        """Yield (dest_partition, item_index_array) border copies. Default:
        none (self-contained partitions, e.g. hash partitioning)."""
        return ()

    # -- device (jax) hooks: the engine="device" map stage -----------------

    def assign_device(self, items):
        """jnp version of ``assign`` ([n, d] device array -> [n] int32).
        Default: round-trips through the host ``assign``."""
        return jnp.asarray(self.assign(np.asarray(items)), jnp.int32)

    def sort_key_device(self, items):
        """Optional [n] secondary sort key: rows within a partition land in
        this order, which tightens the per-tile boxes the pair reduces skip
        by (``ZonePartitioner`` returns RA). The cluster shuffle quantises it
        over the fixed range ``[-pi, pi]`` (an angle; keys outside clip to
        its ends). Order never affects results — partition reductions are
        commutative sums — so ``None`` (arrival order) is always correct."""
        return None

    def bucket_entries_device(self, items, keys, n_parts: int):
        """-> (dest [m] int32, src [m] int32, valid [m] bool): every
        (partition, item) bucket entry — owned points plus border copies —
        with a static entry count ``m`` so the whole stream can be bucketed
        by one argsort. Default: owned entries device-side, replicas (if
        any) via the host ``replicas`` hook."""
        n = keys.shape[0]
        idx = jnp.arange(n, dtype=jnp.int32)
        reps = list(self.replicas(np.asarray(items), np.asarray(keys),
                                  n_parts))
        if not reps:
            return keys, idx, jnp.ones((n,), bool)
        r_dest = np.concatenate(
            [np.full(len(i), d, np.int32) for d, i in reps] or
            [np.zeros(0, np.int32)])
        r_src = np.concatenate([np.asarray(i, np.int32) for _, i in reps])
        dest = jnp.concatenate([keys, jnp.asarray(r_dest)])
        src = jnp.concatenate([idx, jnp.asarray(r_src)])
        return dest, src, jnp.ones((dest.shape[0],), bool)


@dataclasses.dataclass(frozen=True)
class HashPartitioner(Partitioner):
    """Key mod n_parts on the first column — Hadoop's default partitioner."""

    n_parts: int

    def n_partitions(self, items):
        return self.n_parts

    def assign(self, items):
        key = items[:, 0] if items.ndim > 1 else items
        return (np.asarray(key).astype(np.int64) % self.n_parts
                ).astype(np.int32)

    def assign_device(self, items):
        key = items[:, 0] if items.ndim > 1 else items
        return key.astype(jnp.int32) % self.n_parts


class Reducer:
    """Reduce stage: a per-partition kernel (traced under ``lax.map`` /
    ``shard_map``, so fixed output shape) plus a host-side ``finalize``.
    Partition results are combined by summation (psum across the mesh)."""

    pad_value: float = 0.0   # fill for capacity padding; pick one kernels ignore

    def per_partition(self, owned_p, bucket_p):
        """[C1, d], [C2, d] -> fixed-shape array, summed over partitions."""
        raise NotImplementedError

    def reduce_partitions(self, owned, bucket, n_owned, n_bucket):
        """Batched reduce over a whole size tier: [P, C1, d], [P, C2, d] +
        [P] real counts -> the partition-summed result. Rows at index >=
        count are capacity padding and MUST not contribute.

        Default: re-mask padding to ``pad_value`` and ``lax.map`` the
        per-partition kernel (correct for any reducer). Override with a
        masked batched kernel (leading partition axis) for the hot path.
        """
        mo = jnp.arange(owned.shape[1], dtype=jnp.int32) < n_owned[:, None]
        mb = jnp.arange(bucket.shape[1], dtype=jnp.int32) < n_bucket[:, None]
        owned = jnp.where(mo[..., None], owned, self.pad_value)
        bucket = jnp.where(mb[..., None], bucket, self.pad_value)
        outs = jax.lax.map(lambda ab: self.per_partition(ab[0], ab[1]),
                           (owned, bucket))
        return jax.tree.map(lambda o: jnp.sum(o, axis=0), outs)

    def reduce_traceable(self) -> bool:
        """Whether ``reduce_partitions`` is pure traced jax — callable inside
        a ``shard_map`` region. The default masked ``lax.map`` is; reducers
        that delegate to the blocked engine (host-side block
        planning) are not, and the sharded reduce falls back to eager
        per-shard slicing with a psum combine of the partials."""
        return True

    def tile_pairs(self, total):
        """The ``[2]`` tile pairs (scored, real) inside a device-engine
        total, for a reducer whose kernel skips tile pairs; else None."""
        return None

    def finalize(self, total, sd: "ShuffledData"):
        """Host-side post-combine (dedup corrections, differencing, ...)."""
        return np.asarray(total)

    def flops(self, sd: "ShuffledData") -> float:
        """Estimated reduce-stage FLOPs, for StageStats/Amdahl accounting."""
        return 0.0

    def combiner(self):
        """Map-side combine plugin (an ``executor.Combiner``) for this
        reducer, or None when per-split reduce outputs cannot be merged into
        the whole-catalog answer (any reducer whose kernel couples rows
        ACROSS items, e.g. pair counting — a pair spanning two splits is
        seen by neither split alone). Reducers whose output is a
        commutative-monoid fold over individual owned rows (wordcount's
        token histogram) return one, and the streaming executor then keeps
        only the combined accumulator across splits."""
        return None


class _PaddingAccounting:
    """Shared padded-vs-real capacity accounting (both engines' ShuffledData
    expose these; reducer ``flops`` estimates are written against them)."""

    @property
    def pair_cells(self) -> float:
        """Total padded (owned x bucket) cells the reduce kernels cover."""
        raise NotImplementedError

    @property
    def owned_cells(self) -> float:
        """Total padded owned-capacity rows."""
        raise NotImplementedError

    @property
    def real_pair_cells(self) -> float:
        no = np.asarray(self.n_owned, np.float64)
        nb = np.asarray(self.n_bucket, np.float64)
        return float(np.sum(no * nb))

    @property
    def padded_ratio(self) -> float:
        """pair_cells / real_pair_cells — how much compute the capacity
        padding inflates (the bigger-blocks inversion in one number)."""
        real = self.real_pair_cells
        return self.pair_cells / real if real else 1.0


@dataclasses.dataclass
class ShuffledData(_PaddingAccounting):
    """Post-shuffle state: fixed-capacity padded per-partition arrays."""

    owned: np.ndarray          # [P, C1, d] (pad_value-padded)
    bucket: np.ndarray         # [P, C2, d] owned + replicas (pad_value-padded)
    n_owned: np.ndarray        # [P] int32 real counts
    n_bucket: np.ndarray       # [P] int32 real counts

    @property
    def pair_cells(self) -> float:
        P, C1, _ = self.owned.shape
        return float(P) * C1 * self.bucket.shape[1]

    @property
    def owned_cells(self) -> float:
        return float(self.owned.shape[0]) * self.owned.shape[1]


@dataclasses.dataclass
class TierData:
    """One capacity size-class of the device shuffle: all partitions whose
    bucket fits in C2 rows, padded to one [Pt, C*, ...] layout. Under a
    ``data``-axis mesh, ``Pt`` is rounded up to a multiple of the axis size
    with *phantom* partitions (all-padding rows, zero real counts) so the
    tier splits evenly across shards; the masked kernels ignore them."""

    part_ids: np.ndarray       # [P_real] global partition ids (host)
    owned_wire: tuple          # codec wire arrays, leading dims [Pt, C1]
    bucket_wire: tuple         # codec wire arrays, leading dims [Pt, C2]
    n_owned: jax.Array         # [Pt] int32 real counts (device; 0 = phantom)
    n_bucket: jax.Array        # [Pt] int32 real counts (device; 0 = phantom)
    C1: int = 0
    C2: int = 0
    Pt: int = 0                # padded partition rows (multiple of n_shards)

    @property
    def nbytes(self) -> int:
        return sum(int(w.size) * w.dtype.itemsize
                   for w in (*self.owned_wire, *self.bucket_wire))


@dataclasses.dataclass
class DeviceShuffledData(_PaddingAccounting):
    """Post-shuffle state of the device engine: wire-dtype payloads grouped
    into capacity tiers. ``n_owned``/``n_bucket`` are the global per-partition
    real counts (host arrays), so reducer ``finalize`` hooks work unchanged
    across engines."""

    tiers: list
    n_owned: np.ndarray        # [P] int32 (host)
    n_bucket: np.ndarray       # [P] int32 (host)

    @property
    def pair_cells(self) -> float:
        return float(sum(t.Pt * t.C1 * t.C2 for t in self.tiers))

    @property
    def owned_cells(self) -> float:
        return float(sum(t.Pt * t.C1 for t in self.tiers))


@dataclasses.dataclass
class MapReduceJob:
    """A named composition of the three pluggable stages. ``codec`` names a
    registered codec (or is one); ``tile`` is a positive int, checked here
    so every entry point refuses a bad one before any work runs."""

    name: str
    partitioner: Partitioner
    reducer: Reducer
    codec: str | ShuffleCodec = "identity"
    tile: int = 256            # capacity quantum (the paper's block size)

    def __post_init__(self):
        check_positive_int("tile", self.tile, 256)


def check_positive_int(name: str, value, example: int) -> None:
    """Refuse a setting that is not a positive int (a stale ``"auto"``
    included) before any work runs, naming the concrete form."""
    if (isinstance(value, bool) or not isinstance(value, (int, np.integer))
            or value < 1):
        raise ValueError(f"{name} must be a positive int such as "
                         f"{name}={example}, got {value!r}")


@dataclasses.dataclass
class JobResult:
    output: object
    stats: StageStats


# ---------------------------------------------------------------------------
# Host engine (oracle parity + mesh sharding)
# ---------------------------------------------------------------------------

def shuffle_stage(items, partitioner: Partitioner, codec="identity", *,
                  tile: int = 256, pad_partitions_to: int = 1,
                  pad_value: float = 0.0,
                  stats: StageStats | None = None) -> ShuffledData:
    """Map (assign + replicate) then shuffle (codec wire trip, pad, stack).

    The codec round-trips the payload exactly as the wire would see it —
    except for *exact* codecs (``identity``), whose no-op encode/decode is
    skipped entirely (``ShuffleCodec.roundtrip``); ``shuffle_wire_bytes``
    always comes from the static ``codec.nbytes`` formula, so no encoded
    copy is ever materialized just for accounting. Wire bytes count every
    point that lands in a bucket (owned + border copies), matching the
    paper's "bytes that crossed the shuffle" accounting.
    """
    codec = get_codec(codec)
    items = np.asarray(items)
    if items.ndim == 1:
        items = items[:, None]
    stats = stats if stats is not None else StageStats()

    tr = get_tracer()
    with tr.span("map", cat="stage", engine="host"):
        t0 = time.perf_counter()
        P = int(partitioner.n_partitions(items))
        keys = np.asarray(partitioner.assign(items))
        owned_idx = [np.flatnonzero(keys == k) for k in range(P)]
        bucket_idx = [[idx] for idx in owned_idx]
        for dest, idx in partitioner.replicas(items, keys, P):
            bucket_idx[dest].append(np.asarray(idx))
        t1 = time.perf_counter()
    stats.map_wall_s = t1 - t0
    stats.map_bytes = items.nbytes

    with tr.span("shuffle", cat="stage", engine="host"):
        t0 = time.perf_counter()
        decoded = codec.roundtrip(items).astype(np.float32)
        P_pad = _round_up(P, pad_partitions_to)
        d = items.shape[1]
        owned_lists = [decoded[i] for i in owned_idx]
        bucket_lists = [decoded[np.concatenate(parts)]
                        for parts in bucket_idx]
        empty = np.zeros((0, d), np.float32)
        owned_lists += [empty] * (P_pad - P)
        bucket_lists += [empty] * (P_pad - P)
        C1 = _round_up(max(len(o) for o in owned_lists), tile)
        C2 = _round_up(max(len(b) for b in bucket_lists), tile)
        sd = ShuffledData(
            owned=np.stack([_pad_rows(o, C1, pad_value)
                            for o in owned_lists]),
            bucket=np.stack([_pad_rows(b, C2, pad_value)
                             for b in bucket_lists]),
            n_owned=np.array([len(o) for o in owned_lists], np.int32),
            n_bucket=np.array([len(b) for b in bucket_lists], np.int32),
        )
        n_shuffled = int(sd.n_bucket.sum())
        t1 = time.perf_counter()
    stats.shuffle_wall_s = t1 - t0
    stats.shuffle_wire_bytes = codec.nbytes(n_shuffled * d)
    stats.shuffle_raw_bytes = 4 * n_shuffled * d
    stats.n_items = len(items)
    stats.n_partitions = P_pad
    stats.codec = codec.name
    stats.engine = "host"
    stats.shuffle_index_impl = "numpy"     # the host shuffle is all numpy
    return sd


def reduce_stage(reducers, sd: ShuffledData, mesh=None):
    """Run every reducer's per-partition kernel in ONE pass over the buckets
    (multi-job batching), summing over partitions — sharded over the mesh's
    ``data`` axis with a psum combine when a mesh is given. -> tuple of
    per-reducer totals."""
    owned, bucket = jnp.asarray(sd.owned), jnp.asarray(sd.bucket)

    def per_part(o, b):
        return tuple(r.per_partition(o, b) for r in reducers)

    if _data_axis_size(mesh) == 1:
        outs = jax.lax.map(lambda ab: per_part(ab[0], ab[1]), (owned, bucket))
        return jax.tree.map(lambda o: jnp.sum(o, axis=0), outs)

    from jax.sharding import PartitionSpec as P

    def body(o, b):
        r = jax.lax.map(lambda ab: per_part(ab[0], ab[1]), (o, b))
        return jax.tree.map(lambda x: jax.lax.psum(jnp.sum(x, axis=0),
                                                   "data"), r)

    D = _data_axis_size(mesh)
    assert owned.shape[0] % D == 0, (owned.shape, dict(mesh.shape))
    spec = P("data", None, None)
    return _shard_map_compat(
        body, mesh=mesh, in_specs=(spec, spec),
        out_specs=tuple(P() for _ in reducers),
        axis_names=frozenset({"data"}))(owned, bucket)


# ---------------------------------------------------------------------------
# Device engine (the hot path): wire-dtype shuffle + tiered masked reduce
# ---------------------------------------------------------------------------

def plan_tiers(n_owned, n_bucket, tile: int, max_tiers: int = 3,
               pad_partitions_to: int = 1):
    """Group partitions into <= ``max_tiers`` capacity size classes.

    One global capacity (the host engine's choice) is sized by the most
    skewed partition, so every partition pays the worst partition's padding
    — the bigger-blocks inversion. Tiers bound that: partitions are
    grouped by bucket capacity (rounded to the ``tile`` quantum) and each
    tier is padded only to ITS max. The <=2 split points are chosen by
    exact search over distinct capacities, minimizing total tier cost: the
    padded pair cells ``Pt * C1 * C2`` (``Pt`` = phantom-padded partition
    count).

    ``pad_partitions_to`` (the mesh's ``data`` axis size): each tier's
    partition count is rounded up to a multiple of it with phantom
    all-padding partitions so the tier splits evenly across shards; the
    cost search charges those phantom rows, so under a wide mesh the
    planner leans toward fewer, fuller tiers.

    The search is a vectorized scan over the O(U^2) segment-cost table of
    unique capacities (the old ``itertools.combinations`` python loop was
    O(U choose 2) cost evaluations — minutes at U=500), with an early-exit
    bound: any prefix tier already costing >= the incumbent best prunes
    every deeper split under it.

    -> list of (part_ids ascending, C1, C2) per tier (part_ids are REAL
    partitions only; the engine appends the phantoms).
    """
    n_owned = np.asarray(n_owned, np.int64)
    n_bucket = np.asarray(n_bucket, np.int64)
    pad = pad_partitions_to
    caps = np.array([_round_up(int(c), tile) for c in n_bucket], np.int64)
    uniq = np.unique(caps)
    U = len(uniq)

    def build(cut_ids):
        tiers, lo = [], -1
        for th in (int(uniq[i]) for i in cut_ids):
            sel = np.flatnonzero((caps > lo) & (caps <= th))
            lo = th
            if len(sel):
                tiers.append((sel, _round_up(int(n_owned[sel].max()), tile),
                              th))
        return tiers

    # Segment-cost table: S[i, j] = cost of one tier covering uniq[i..j]
    # (inclusive; +inf below the diagonal). Costs are exact in float64 —
    # padded-cell counts are integers far below 2**53 — so argmin over S
    # reproduces the python accumulation bit-for-bit.
    ui = np.searchsorted(uniq, caps)
    maxo = np.zeros(U, np.int64)
    np.maximum.at(maxo, ui, n_owned)
    pc = np.concatenate([[0], np.cumsum(np.bincount(ui, minlength=U))])
    row = np.arange(U)[:, None]
    col = np.arange(U)[None, :]
    seg_max = np.maximum.accumulate(
        np.where(col >= row, maxo[None, :], 0), axis=1)
    cnt = pc[1:][None, :] - pc[:-1][:, None]
    Pt = np.maximum(pad, -(-cnt // pad) * pad).astype(np.float64)
    C1 = np.maximum(tile, -(-seg_max // tile) * tile).astype(np.float64)
    C2 = np.broadcast_to(uniq.astype(np.float64)[None, :], (U, U))
    S = np.where(col >= row, Pt * C1 * C2, np.inf)

    best_cost = float(S[0, U - 1])
    best_cuts = (U - 1,)
    if max_tiers >= 2 and U >= 2:
        two = S[0, :U - 1] + S[1:, U - 1]
        c = int(np.argmin(two))          # first occurrence = lexicographic
        if two[c] < best_cost:
            best_cost, best_cuts = float(two[c]), (c, U - 1)
    if max_tiers >= 3 and U >= 3:
        a = S[0, :U - 2]                 # prefix tier ending at cut c1
        keep = a < best_cost             # early-exit bound: prefix alone
        if keep.any():                   # >= incumbent prunes the row
            T = ((a[:, None] + S[1:U - 1, 1:U - 1])
                 + S[2:, U - 1][None, :])
            r2 = np.arange(U - 2)
            T = np.where((r2[:, None] <= r2[None, :]) & keep[:, None],
                         T, np.inf)
            flat = int(np.argmin(T))
            c1, c2 = divmod(flat, U - 2)
            if T[c1, c2] < best_cost:
                best_cost = float(T[c1, c2])
                best_cuts = (c1, c2 + 1, U - 1)
    if max_tiers > 3 and U > 3:
        # deeper splits are rare; exact DFS with the same early-exit bound
        kmax = min(max_tiers, U)

        def dfs(i0, cuts, prefix):
            nonlocal best_cost, best_cuts
            if prefix >= best_cost:
                return
            close = prefix + S[i0, U - 1]
            if close < best_cost:
                best_cost, best_cuts = float(close), tuple(cuts) + (U - 1,)
            if len(cuts) + 2 <= kmax:
                for c in range(i0, U - 1):
                    dfs(c + 1, cuts + [c], prefix + S[i0, c])

        dfs(0, [], 0.0)
    return build(best_cuts)


def _sort_keys(ids, skey, n_ids: int):
    """One int32 sort key per entry: the partition id (``0..n_ids - 1``) in
    the high bits, ``skey`` quantised linearly over its range in the
    ``B`` bits below, where ``n_ids * 2^B <= 2^31``. Quantising is monotone,
    so an argsort orders each partition by ``skey`` (ties in arrival
    order)."""
    bits = 31 - max(n_ids - 1, 1).bit_length()
    lo, hi = jnp.min(skey), jnp.max(skey)
    scale = (2.0 ** bits - 1.0) / jnp.maximum(hi - lo, 1e-30)
    q = jnp.floor((skey - lo) * scale).astype(jnp.int32)   # float32 rounds
    return (ids << bits) | jnp.clip(q, 0, 2 ** bits - 1)    # 2^B - 1 up


@functools.partial(jax.jit, static_argnames=("specs",))
def _scatter_tiers_jit(payloads, keys, dest_eff, src, skey, owned_starts,
                       bucket_starts, part_tier, part_local, *, specs):
    """Argsort-based bucketing: sort owned rows by partition and bucket
    entries by destination, compute each entry's rank within its partition
    from the exclusive-cumsum starts, and scatter the *wire-dtype* payload
    rows into every tier's padded [Pt, C, ...] layout (entries outside the
    tier drop out of range).

    ``dest_eff`` is [m] with invalid entries set to P (they sort last and
    hit ``part_tier[P] == -1``, so no tier claims them).

    Within a partition, rows and bucket entries lie in the order of the
    partitioner's secondary key ``skey`` (RA for zones, so the pair kernel's
    tile windows are short), or in arrival order where it has none. Each is
    ONE argsort of an int32 key (``_sort_keys``): a two-key sort costs the
    TPU compiler ~100 s more than a one-key argsort.
    """
    n, m = keys.shape[0], dest_eff.shape[0]
    if skey is None or not n:
        ko = jnp.argsort(keys)
        bo = jnp.argsort(dest_eff)
    else:
        n_ids = owned_starts.shape[0]             # P partitions + the P slot
        ko = jnp.argsort(_sort_keys(keys, skey, n_ids))
        bo = jnp.argsort(_sort_keys(dest_eff, skey[src], n_ids))
    sk = keys[ko]
    orank = jnp.arange(n, dtype=jnp.int32) - owned_starts[sk]
    sd = dest_eff[bo]
    brank = jnp.arange(m, dtype=jnp.int32) - bucket_starts[sd]
    own_rows = tuple(p[ko] for p in payloads)
    bkt_rows = tuple(p[src[bo]] for p in payloads)

    def scatter(rows, pos, Pt, C):
        return tuple(
            jnp.zeros((Pt * C,) + r.shape[1:], r.dtype)
            .at[pos].set(r, mode="drop")
            .reshape((Pt, C) + r.shape[1:]) for r in rows)

    out = []
    for t, (Pt, C1, C2) in enumerate(specs):
        o_pos = jnp.where(part_tier[sk] == t,
                          part_local[sk] * C1 + orank, Pt * C1)
        b_pos = jnp.where(part_tier[sd] == t,
                          part_local[sd] * C2 + brank, Pt * C2)
        out.append((scatter(own_rows, o_pos, Pt, C1),
                    scatter(bkt_rows, b_pos, Pt, C2)))
    return tuple(out)


# On a CPU-only backend the XLA sort/scatter compiles cost more than the
# whole shuffle; index *metadata* ([m] int32 permutations) is then computed
# with vectorized numpy and only the payload moves through jax gathers.
# Accelerator backends keep the pure-jnp path so the payload AND its
# bucketing stay device-resident. Tests pin this to exercise both paths.
# The RESOLVED choice is recorded in ``StageStats.shuffle_index_impl``
# ("jnp" | "host") so an "auto" run under a mesh is never ambiguous about
# which path produced its shuffle metadata; both paths must produce
# identical tier shapes and results (asserted in tests and md_check). Both
# order each partition by the partitioner's secondary key: the host path by
# the key itself, the jnp path by the key quantised to the bits the
# partition id leaves free (``_sort_keys``), so rows whose keys share a
# quantum may lie in another order. A mesh always takes the jnp path
# (``_exchange_jit``), whose owners order by the key quantised over the
# fixed range [-pi, pi] (``_ra_quanta``), one quantum for every chip and job.
SHUFFLE_INDEX_IMPL = "auto"            # "auto" | "jnp" | "host"


def _use_jnp_indices() -> bool:
    if SHUFFLE_INDEX_IMPL == "auto":
        return jax.default_backend() != "cpu"
    return SHUFFLE_INDEX_IMPL == "jnp"


def _scatter_tiers_host(payloads, keys_h, dest_h, src_h, skey_h, o_starts,
                        b_starts, part_tier, part_local, specs):
    """numpy twin of ``_scatter_tiers_jit``: same argsort/rank math on the
    index metadata, then one jax *gather* per tier (gather maps point padding
    at row n, a zeros sentinel appended to the payload)."""
    n = keys_h.shape[0]
    if skey_h is not None:
        ko = np.lexsort((skey_h, keys_h))
        bo = np.lexsort((skey_h[src_h], dest_h))
    else:
        ko = np.argsort(keys_h, kind="stable")
        bo = np.argsort(dest_h, kind="stable")
    sk = keys_h[ko]
    orank = np.arange(n, dtype=np.int32) - o_starts[sk]
    sd = dest_h[bo]
    brank = np.arange(len(dest_h), dtype=np.int32) - b_starts[sd]
    ssrc = src_h[bo]
    # numpy fancy indexing + one host->device put per tier array: on CPU this
    # beats XLA's eager gather ~5x, and this path only runs on CPU backends
    padded = tuple(np.concatenate(
        [np.asarray(p), np.zeros((1,) + p.shape[1:], p.dtype)])
        for p in payloads)

    def gather(rows, sel_part, rank, srcs, t, Pt, C):
        sel = part_tier[sel_part] == t
        g = np.full(Pt * C, n, np.int32)
        g[part_local[sel_part[sel]] * C + rank[sel]] = srcs[sel]
        return tuple(jnp.asarray(p[g].reshape((Pt, C) + p.shape[1:]))
                     for p in rows)

    out = []
    for t, (Pt, C1, C2) in enumerate(specs):
        out.append((gather(padded, sk, orank, ko.astype(np.int32), t, Pt, C1),
                    gather(padded, sd, brank, ssrc, t, Pt, C2)))
    return tuple(out)


# ---------------------------------------------------------------------------
# Cluster shuffle: per-chip blocks, one all-to-all to the partition owners
# ---------------------------------------------------------------------------
#
# Under a ``data`` axis of size D every entry of the mapped stream, an owned
# row (id ``key``) or a bucket entry (id ``P + 1 + dest``), is sent once to
# the chip that owns its partition's tier slot, and lands there at its final
# row of that chip's slice of the tier. The host plans from per-chip counts
# alone; the rows never leave the chips.

_ROW = PartitionSpec("data")


@functools.partial(jax.jit, static_argnames=("mesh", "n_parts"))
def _count_entries(keys, dest_eff, *, mesh, n_parts):
    """-> ``[D, 2 (P + 1)]`` int32: each chip's owned rows per key, then its
    bucket entries per destination (index ``P`` counts padding)."""
    def body(k, d):
        return jnp.concatenate([jnp.bincount(k, length=n_parts + 1),
                                jnp.bincount(d, length=n_parts + 1)]
                               ).astype(jnp.int32)[None]

    return _shard_map_compat(body, mesh=mesh, in_specs=(_ROW, _ROW),
                             out_specs=_ROW, axis_names=frozenset({"data"}))(
        keys, dest_eff)


def _segment_starts(layout) -> np.ndarray:
    """The first row of every partition slot's owned and bucket rows in a
    chip's flat tier buffer (ascending), then the buffer's length ``L``."""
    starts = []
    for q, C1, C2, o0, b0 in layout:
        starts += [o0 + l * C1 for l in range(q)]
        starts += [b0 + l * C2 for l in range(q)]
    starts.append(max((b0 + q * C2 for q, _, C2, _, b0 in layout),
                      default=0))
    return np.asarray(starts, np.int32)


def _ra_quanta(skey, bits: int):
    """``skey`` quantised linearly over the fixed range ``[-pi, pi]`` to
    ``bits`` bits (monotone, so order is kept up to ties)."""
    scale = (2.0 ** bits - 1.0) / (2.0 * np.pi)
    q = jnp.floor((skey + np.pi) * scale).astype(jnp.int32)
    return jnp.clip(q, 0, 2 ** bits - 1)


def _order_arrivals(pos, quanta, starts, bits: int):
    """Re-place arrived entries so each segment (a slot's owned or bucket
    rows, ``starts``) lies in ``quanta`` order. A segment's arrivals fill
    its first rows whatever their order, so an entry's new row is its
    segment's start plus its rank in the segment: ONE argsort of an int32
    key, the segment in the high bits and ``quanta`` in the ``bits`` below
    (a two-key sort costs the TPU compiler ~100 s more). Unused slots
    (``pos >= L``) form the last segment, and keep distinct rows past
    ``L``."""
    seg = jnp.searchsorted(starts, pos, side="right").astype(jnp.int32) - 1
    key, perm = jax.lax.sort_key_val(
        (seg << bits) | quanta, jnp.arange(pos.shape[0], dtype=jnp.int32))
    ss = key >> bits
    first = jnp.searchsorted(ss, jnp.arange(starts.shape[0],
                                            dtype=jnp.int32)).astype(jnp.int32)
    rank = jnp.arange(pos.shape[0], dtype=jnp.int32) - first[ss]
    return jnp.zeros_like(pos).at[perm].set(starts[ss] + rank,
                                            unique_indices=True)


@functools.partial(jax.jit, static_argnames=("mesh", "cap", "layout"))
def _exchange_jit(payloads, keys, dest_eff, src, skey, gid, send_base,
                  dest_base, *, mesh, cap, layout):
    """The cluster shuffle, one program: on each chip, sort its entries by
    owner, pack them into ``D`` send buffers of ``cap`` rows, exchange the
    buffers with one ``all_to_all``, and scatter what arrived into the
    chip's slice of every tier.

    ``gid`` ([2 (P + 1)], the same on every chip) orders the entry ids by
    owning chip; ``send_base``/``dest_base`` ([D, 2 (P + 1)], one row per
    chip, from ``_exchange_plan``) turn an entry's position in that order
    into its send slot and its row in the owner's flat tier buffer.
    ``layout``: per tier ``(q, C1, C2, owned_start, bucket_start)``, with
    ``q`` partitions a chip. -> per tier (owned wire, bucket wire), each
    ``[Pt, C, ...]`` sharded over ``data``.

    ``skey`` ([n] per chip, the partitioner's sort key, or ``None``): each
    entry carries its key quantised over ``[-pi, pi]`` (``_ra_quanta``) as
    an int32 column beside its destination row (no collective more), and
    the owner lays every partition's owned rows and bucket entries out in
    that order (``_order_arrivals``): RA for zones, so the pair kernel's
    tile windows are short. The order has to be made on the owner, since a
    partition arrives in ``D`` runs, one a sender. With ``None`` each
    partition keeps its arrival order: chip order, and each chip's entries
    in stream order."""
    D = _data_axis_size(mesh)
    n_parts = gid.shape[0] // 2 - 1
    starts = _segment_starts(layout)
    L = int(starts[-1])
    bits = 31 - (len(starts) - 1).bit_length()      # segment ids 0..nseg

    def body(payloads, keys, dest_eff, src, skey, gid, send_base, dest_base):
        ids = jnp.concatenate([keys, dest_eff + (n_parts + 1)])
        rows = jnp.concatenate([jnp.arange(keys.shape[0], dtype=jnp.int32),
                                src])
        g = gid[ids]
        order = jnp.argsort(g)
        gs = g[order]
        i = jnp.arange(ids.shape[0], dtype=jnp.int32)
        slot = send_base[0, gs] + i          # >= D * cap: not sent
        take = rows[order]
        send = [jnp.zeros((D * cap,) + p.shape[1:], p.dtype)
                .at[slot].set(p[take], mode="drop", unique_indices=True)
                for p in payloads]
        # unused slots point past the tier buffer, each at its own row
        unused = (L + jax.lax.axis_index("data") * (D * cap)
                  + jnp.arange(D * cap, dtype=jnp.int32))
        dest = dest_base[0, gs] + i
        if skey is not None:      # the key rides beside the destination row
            unused = jnp.stack([unused, jnp.zeros_like(unused)], axis=1)
            dest = jnp.stack([dest, _ra_quanta(skey, bits)[take]], axis=1)
        send.append(unused.at[slot].set(dest, mode="drop",
                                        unique_indices=True))
        recv = [jax.lax.all_to_all(x, "data", 0, 0, tiled=True)
                for x in send]
        pos = recv.pop()
        if skey is not None:
            pos = _order_arrivals(pos[:, 0], pos[:, 1], jnp.asarray(starts),
                                  bits)
        flat = [jnp.zeros((L,) + r.shape[1:], r.dtype)
                .at[pos].set(r, mode="drop", unique_indices=True)
                for r in recv]

        def cut(start, q, C):
            return tuple(f[start:start + q * C].reshape((q, C) + f.shape[1:])
                         for f in flat)

        return tuple((cut(o0, q, C1), cut(b0, q, C2))
                     for q, C1, C2, o0, b0 in layout)

    return _shard_map_compat(
        body, mesh=mesh,
        in_specs=(_ROW, _ROW, _ROW, _ROW, _ROW, PartitionSpec(), _ROW, _ROW),
        out_specs=_ROW, axis_names=frozenset({"data"}))(
        payloads, keys, dest_eff, src, skey, gid, send_base, dest_base)


def _exchange_plan(counts, plan, specs, D: int):
    """Host side of the exchange, from ``counts`` (``_count_entries``) and
    the tier plan. A tier's partition slot ``l`` lives on chip ``l // q``;
    entries of one id keep chip order (chip 0's first).

    -> (gid, send_base, dest_base, cap, layout, rows_crossing): the
    tables ``_exchange_jit`` takes, the send buffer's capacity (rounded up
    so a catalog that moves slightly does not compile anew), the tier
    layout of each chip, and the entries whose owner is not their chip."""
    counts = np.asarray(counts, np.int64)
    G = counts.shape[1]
    n_parts = G // 2 - 1
    owner = np.full(G, D, np.int64)              # D: padding, never sent
    base = np.zeros(G, np.int64)
    layout, off = [], 0
    for (ids, C1, C2), (Pt, _, _) in zip(plan, specs):
        q = Pt // D
        local = np.arange(len(ids))
        o0, b0 = off, off + q * C1
        owner[ids] = owner[n_parts + 1 + ids] = local // q
        base[ids] = o0 + (local % q) * C1
        base[n_parts + 1 + ids] = b0 + (local % q) * C2
        layout.append((q, C1, C2, o0, b0))
        off = b0 + q * C2
    order = np.argsort(owner, kind="stable")
    gid = np.empty(G, np.int32)
    gid[order] = np.arange(G, dtype=np.int32)
    og = owner[order]
    cg = counts[:, order]                        # [D, G] in gid order
    start = np.cumsum(cg, axis=1) - cg           # first sorted position
    per_owner = np.stack([np.bincount(og, weights=c, minlength=D + 1)
                          for c in cg]).astype(np.int64)
    owner_start = np.cumsum(per_owner, axis=1) - per_owner
    widest = max(int(per_owner[:, :D].max()), 1)
    cap = _round_up(widest, max(8, 1 << max(widest.bit_length() - 6, 0)))
    send_base = np.where(og < D, og * cap - np.take_along_axis(
        owner_start, np.broadcast_to(og, cg.shape), axis=1), D * cap)
    before = np.cumsum(counts, axis=0) - counts  # rows of lower chips
    dest_base = base[order] + before[:, order] - start
    crossing = int(counts[(owner < D) & (owner != np.arange(D)[:, None])]
                   .sum())
    return (gid, send_base.astype(np.int32), dest_base.astype(np.int32), cap,
            tuple(layout), crossing)


def _sharded_rows(parts, mesh, length: int, fill, like):
    """One ``[D * length, ...]`` array sharded over ``data`` from per-chip
    pieces (piece ``c``, padded with ``fill`` to ``length`` rows, on the
    chips of shard ``c``; ``None`` is all padding). Rows stay on their
    chips."""
    devs = _data_devices(mesh)
    shards = []
    for x, devices in zip(parts, devs):
        if x is None:
            x = jnp.full((length,) + like.shape[1:], fill, like.dtype,
                         device=devices[0])
        elif x.shape[0] < length:
            x = jnp.pad(x, [(0, length - x.shape[0])] + [(0, 0)] * (x.ndim - 1),
                        constant_values=fill)
        shards += [jax.device_put(x, d) for d in devices]
    return jax.make_array_from_single_device_arrays(
        (len(devs) * length,) + like.shape[1:], NamedSharding(mesh, _ROW),
        shards)


def _make_sharded_body(reducers, codec, mesh):
    """shard_map'd decode + masked reduce + psum for traceable reducers."""
    from jax.sharding import PartitionSpec as P

    def body(ow, bw, no, nb):
        owned = codec.decode_device(*ow)
        bucket = codec.decode_device(*bw)
        outs = tuple(r.reduce_partitions(owned, bucket, no, nb)
                     for r in reducers)
        return jax.tree.map(lambda x: jax.lax.psum(x, "data"), outs)

    shard = P("data")                   # prefix spec: shard axis 0, rest repl
    return _shard_map_compat(
        body, mesh=mesh, in_specs=(shard, shard, shard, shard),
        out_specs=P(), axis_names=frozenset({"data"}))


def _make_psum_combine(mesh):
    """shard_map'd psum of stacked [D, ...] per-shard partial pytrees."""
    from jax.sharding import PartitionSpec as P

    def combine(t):
        return jax.tree.map(
            lambda x: jax.lax.psum(jnp.sum(x, axis=0), "data"), t)

    return _shard_map_compat(combine, mesh=mesh, in_specs=(P("data"),),
                             out_specs=P(), axis_names=frozenset({"data"}))


# The shard_map'd callables must be REUSED across calls for jit's internal
# shape cache to hit (it keys on function identity; a fresh closure per
# run_job would retrace + recompile every tier of every run). Keys are
# hashable for the stock stages (frozen-dataclass reducers, registry codec
# singletons, meshes); unhashable custom stages fall back to an uncached
# build and pay the retrace.
_make_sharded_body_cached = functools.lru_cache(maxsize=None)(
    _make_sharded_body)
_make_psum_combine_cached = functools.lru_cache(maxsize=None)(
    _make_psum_combine)


def _reduce_tier_sharded(reducers, codec, tier: TierData, mesh):
    """Reduce one tier across the mesh's ``data`` axis and psum-combine.

    Tier rows are contiguous per shard (shard ``s`` owns rows
    ``[s*Pt/D, (s+1)*Pt/D)``; phantom partitions mask to nothing), and the
    cluster shuffle already left them there. Two sub-paths mirror the
    ``ops.py`` backend split:

    - every reducer traceable (Pallas masked kernels on TPU, pure-jnp
      reducers anywhere): decode + masked reduce + ``lax.psum`` run INSIDE
      one ``shard_map`` region, each shard's kernels on its own device.
    - otherwise (the blocked engine plans its blocks on the host,
      which cannot happen under tracing): each shard's rows are sliced and
      reduced eagerly, then the stacked per-shard partials cross ONE
      ``shard_map`` psum. Bit-identical either way — the accumulators are
      integers and all engines share the ``_dots2d`` score formulation.

    -> tuple of per-reducer totals (replicated).
    """
    D = _data_axis_size(mesh)
    if all(r.reduce_traceable() for r in reducers):
        try:
            fn = _make_sharded_body_cached(reducers, codec, mesh)
        except TypeError:               # unhashable custom reducer/codec
            fn = _make_sharded_body(reducers, codec, mesh)
        return fn(tier.owned_wire, tier.bucket_wire, tier.n_owned,
                  tier.n_bucket)

    q = tier.Pt // D
    partials = []
    for s in range(D):
        sl = slice(s * q, (s + 1) * q)
        owned = codec.decode_device(*(w[sl] for w in tier.owned_wire))
        bucket = codec.decode_device(*(w[sl] for w in tier.bucket_wire))
        partials.append(tuple(
            r.reduce_partitions(owned, bucket, tier.n_owned[sl],
                                tier.n_bucket[sl]) for r in reducers))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *partials)
    try:
        combine = _make_psum_combine_cached(mesh)
    except TypeError:
        combine = _make_psum_combine(mesh)
    return combine(stacked)


@dataclasses.dataclass
class MappedSplit:
    """Device-resident output of the map stage for ONE catalog split: the
    codec wire payload plus the bucket-entry index metadata. This is the
    unit the streaming executor (``executor.py``) moves between stages —
    splits are mapped one at a time and either reduced immediately (combine
    mode) or accumulated via ``concat_mapped`` and reduced once (the raw
    float32 split can be dropped as soon as its ``MappedSplit`` exists; only
    wire-dtype arrays persist)."""

    payloads: tuple            # codec wire arrays, leading axis = n_rows
    keys: jax.Array            # [n] int32 owning partition per row
    dest_eff: jax.Array        # [m] int32 bucket destinations (invalid -> P)
    src: jax.Array             # [m] int32 row index into payloads
    skey: object               # [n] secondary sort key or None
    n_rows: int = 0
    d: int = 0
    nbytes_in: int = 0         # raw input bytes (map_bytes accounting)


def map_split_device(partitioner: Partitioner, codec: ShuffleCodec, items,
                     P: int) -> MappedSplit:
    """Map stage for one split: partition assignment + border replication as
    jax ops, payload encoded straight to the codec's wire dtype. Pure
    dispatch — nothing here blocks, so a caller can map split k while split
    k-1 still reduces."""
    with get_tracer().span("map", cat="stage", engine="device"):
        if not isinstance(items, jax.Array):
            items = np.asarray(items)
        if items.ndim == 1:
            items = items[:, None]
        items_dev = jnp.asarray(items, jnp.float32)
        keys = partitioner.assign_device(items_dev)
        dest, src, valid = partitioner.bucket_entries_device(items_dev,
                                                            keys, P)
        dest_eff = jnp.where(valid, dest, P).astype(jnp.int32)
        src = jnp.asarray(src, jnp.int32)
        payloads = codec.encode_device(items_dev)
        skey = partitioner.sort_key_device(items_dev)
        return MappedSplit(payloads, keys, dest_eff, src, skey,
                           n_rows=int(items.shape[0]), d=int(items.shape[1]),
                           nbytes_in=int(items.nbytes))


def map_blocks(partitioner: Partitioner, codec: ShuffleCodec, items, P: int,
               mesh) -> list:
    """Map stage of a cluster: the rows cut into one contiguous block per
    shard of the mesh's ``data`` axis (a node's HDFS block), each put on
    its chip and mapped there. -> one ``MappedSplit`` per shard, ``None``
    for an empty block after the first."""
    rows = items if isinstance(items, jax.Array) else np.asarray(items)
    devs = _data_devices(mesh)
    step = -(-rows.shape[0] // len(devs))
    out = []
    for c, devices in enumerate(devs):
        block = rows[c * step:(c + 1) * step]
        out.append(map_split_device(partitioner, codec,
                                    jax.device_put(block, devices[0]), P)
                   if c == 0 or block.shape[0] else None)
    return out


def map_stream(partitioner: Partitioner, codec: ShuffleCodec, items, P: int,
               mesh=None):
    """The map stage for ``mesh``: one ``MappedSplit``, or under a
    ``data`` axis wider than one the per-chip ``map_blocks``."""
    if _data_axis_size(mesh) > 1:
        return map_blocks(partitioner, codec, items, P, mesh)
    return map_split_device(partitioner, codec, items, P)


def concat_streams(streams: list):
    """``concat_mapped`` of map outputs, chip by chip where they are
    ``map_blocks`` lists."""
    if not isinstance(streams[0], list):
        return concat_mapped(streams)
    per_chip = [[b for b in bs if b is not None] for bs in zip(*streams)]
    return [concat_mapped(bs) if bs else None for bs in per_chip]


def concat_mapped(splits: "list[MappedSplit]") -> MappedSplit:
    """Merge per-split map outputs into one stream (device concat; source
    row indices are offset into the concatenated payload). Entry ORDER
    differs from a monolithic map over the concatenated catalog — bucket
    contents are identical as multisets, and partition reductions are
    commutative sums, so results are bit-identical (asserted in tests)."""
    if len(splits) == 1:
        return splits[0]
    offs = np.cumsum([0] + [s.n_rows for s in splits[:-1]])
    skeys = [s.skey for s in splits]
    return MappedSplit(
        payloads=tuple(jnp.concatenate(ps)
                       for ps in zip(*(s.payloads for s in splits))),
        keys=jnp.concatenate([s.keys for s in splits]),
        dest_eff=jnp.concatenate([s.dest_eff for s in splits]),
        src=jnp.concatenate([s.src + np.int32(o)
                             for s, o in zip(splits, offs)]),
        skey=(None if any(sk is None for sk in skeys)
              else jnp.concatenate(skeys)),
        n_rows=int(sum(s.n_rows for s in splits)),
        d=splits[0].d,
        nbytes_in=int(sum(s.nbytes_in for s in splits)))


@dataclasses.dataclass
class ResidentCatalog:
    """Device-resident post-shuffle handle: a catalog mapped and shuffled
    ONCE into tiered wire-dtype partitions that stay on device (sharded over
    the mesh's ``data`` axis when one is given), plus the shuffle signature
    (partitioner / codec / tile / pad_value) that defines which jobs may
    reduce against it.

    ``shuffle_reduce_device`` builds one per call and reduces through it
    immediately — the one-shot path. The MapReduce query service
    (``serving/mr_service.py``) instead keeps one alive across many
    requests, so N queries cost one shuffle ever plus N fused batched
    reduces (which also reuse the module-level jit/shard_map caches — they
    key on reducers/codec/mesh, not on the catalog)."""

    partitioner: Partitioner
    codec: ShuffleCodec
    tile: int
    pad_value: float
    sd: DeviceShuffledData
    P: int
    mesh: object = None
    shard_pad: np.ndarray = None       # [D] padded pair cells per shard
    shard_real: np.ndarray = None      # [D] real pair cells per shard
    n_rows: int = 0
    d: int = 0
    load_stats: StageStats = None      # the shuffle-once cost (set by shuffle_once)

    @property
    def nbytes(self) -> int:
        """Resident wire bytes held on device across requests."""
        return sum(t.nbytes for t in self.sd.tiers)

    def validate(self, jobs) -> None:
        """Jobs must share this catalog's shuffle signature to reduce
        against it (same contract as ``validate_batch``, anchored here)."""
        for j in jobs:
            diffs = [k for k, a, b in [
                ("partitioner", j.partitioner, self.partitioner),
                ("codec", get_codec(j.codec).name, self.codec.name),
                ("tile", j.tile, self.tile),
                ("pad_value", j.reducer.pad_value, self.pad_value),
            ] if a != b]
            if diffs:
                raise ValueError(
                    f"job {j.name!r} cannot reduce against this resident "
                    f"catalog: differs in {', '.join(diffs)}")

    def reduce_totals(self, reducers, stats: StageStats):
        """Tiered masked reduce of ``reducers`` over the resident tiers —
        the reduce half of ``shuffle_reduce_device``, with the same
        accumulate (``+=``) stats contract. Decode happens on-device per
        pass; under a data-axis mesh each tier reduces psum-sharded.

        While anything records (``obs.trace.recording``), the reducers'
        tile pairs (``Reducer.tile_pairs``) are read once the reduce is
        done, into ``stats.pair_tiles_*`` and as counters of the ``reduce``
        span; otherwise they stay on the device, costing no transfer."""
        D = _data_axis_size(self.mesh)
        tr = get_tracer()
        tiles = {}
        with tr.span("reduce", cat="stage", engine="device",
                     tiers=len(self.sd.tiers), counters=tiles):
            t0 = time.perf_counter()
            totals = None
            with tr.span("reduce.dispatch", cat="stage"):
                for tier in self.sd.tiers:
                    if D > 1:
                        outs = _reduce_tier_sharded(reducers, self.codec,
                                                    tier, self.mesh)
                    else:
                        owned = self.codec.decode_device(*tier.owned_wire)
                        bucket = self.codec.decode_device(*tier.bucket_wire)
                        outs = tuple(r.reduce_partitions(owned, bucket,
                                                         tier.n_owned,
                                                         tier.n_bucket)
                                     for r in reducers)
                    totals = outs if totals is None else tuple(
                        jax.tree.map(jnp.add, a, b)
                        for a, b in zip(totals, outs))
            with tr.span("reduce.wait", cat="stage"):
                totals = jax.block_until_ready(totals)
            t1 = time.perf_counter()
            if recording():
                tiles.update(_tile_pairs(reducers, totals))
        stats.pair_tiles_scored += tiles.get("pair_tiles_scored", 0)
        stats.pair_tiles_real += tiles.get("pair_tiles_real", 0)
        stats.reduce_wall_s += t1 - t0
        stats.reduce_bytes += self.nbytes
        stats.reduce_flops += float(sum(r.flops(self.sd) for r in reducers))
        return totals

    def run(self, jobs, stats: StageStats = None) -> "list[JobResult]":
        """Serve ``jobs`` (one or a batch) against the resident tiers with a
        single fused reduce pass — no map, no shuffle: those were paid once
        at ``shuffle_once``. -> one JobResult per job, sharing one
        StageStats whose map/shuffle walls are zero by construction."""
        jobs = [jobs] if isinstance(jobs, MapReduceJob) else list(jobs)
        self.validate(jobs)
        if stats is None:
            stats = StageStats(job="+".join(j.name for j in jobs))
        stats.engine = "device"
        stats.codec = self.codec.name
        stats.n_items = self.n_rows
        stats.n_partitions = self.P
        stats.n_shards = _data_axis_size(self.mesh)
        stats.reduce_padded_ratio = self.sd.padded_ratio
        stats.shard_padded_ratio = tuple(
            float(p / max(r, 1.0))
            for p, r in zip(self.shard_pad, self.shard_real))
        meter = get_meter()
        mtok = meter.begin()
        totals = self.reduce_totals(tuple(j.reducer for j in jobs), stats)
        meter.attribute(mtok, stats)
        return [JobResult(j.reducer.finalize(t, self.sd), stats)
                for j, t in zip(jobs, totals)]


def _tile_pairs(reducers, totals) -> dict:
    """The tile pairs the reducers' kernels scored and those of real rows,
    summed over reducers (one host transfer); {} where none reports any."""
    got = [t for t in (r.tile_pairs(tot) for r, tot in zip(reducers, totals))
           if t is not None]
    if not got:
        return {}
    scored, real = np.sum(np.asarray(jax.device_get(got), np.int64), axis=0)
    return {"pair_tiles_scored": int(scored), "pair_tiles_real": int(real)}


def _shuffle_mapped(partitioner: Partitioner, codec: ShuffleCodec, tile: int,
                    pad_value: float, m, P: int,
                    stats: StageStats, mesh=None) -> ResidentCatalog:
    """Shuffle one mapped stream into device-resident tiers: count, tier,
    argsort-bucket, scatter in wire dtype — the shuffle half of
    ``shuffle_reduce_device``, accumulating (``+=``) into ``stats``.

    Under a ``data`` axis of size D > 1 ``m`` is a ``map_blocks`` list (a
    single ``MappedSplit`` counts as chip 0's block, the others empty): the
    host plans from per-chip counts, and ``_exchange_jit`` moves each entry
    to its owner, so the tiers are born sharded over ``data``. Tier
    partition counts are padded to a multiple of D with phantom
    (zero-count) partitions, so every tier splits evenly across shards.
    -> ResidentCatalog."""
    D = _data_axis_size(mesh)
    if D > 1:
        blocks = list(m) if isinstance(m, list) else [m]
        blocks += [None] * (D - len(blocks))
        m = next(b for b in blocks if b is not None)
        n_rows = sum(b.n_rows for b in blocks if b is not None)
    else:
        n_rows = m.n_rows
    d = m.d
    tr = get_tracer()
    with tr.span("shuffle", cat="stage", engine="device"):
        t0 = time.perf_counter()
        with tr.span("shuffle.wait", cat="stage"):
            if D > 1:
                stream = _blocked_stream(blocks, mesh, P)
                counts = np.asarray(_count_entries(
                    stream.keys, stream.dest_eff, mesh=mesh, n_parts=P))
            else:
                keys_h = np.asarray(jax.block_until_ready(m.keys))
                dest_h = np.asarray(m.dest_eff)
        with tr.span("shuffle.plan", cat="stage"):
            # keys == P marks payload-only rows (carried for the bucket
            # entries that reference them — spilled range reads use this for
            # cross-range border rows); like dest == P they are excluded
            # from owned counts/scatter.
            if D > 1:
                n_owned = counts[:, :P].sum(axis=0, dtype=np.int64)
                n_bucket = counts[:, P + 1:-1].sum(axis=0, dtype=np.int64)
            else:
                n_owned = np.bincount(keys_h, minlength=P + 1)[:P].astype(
                    np.int64)
                n_bucket = np.bincount(dest_h, minlength=P + 1)[:P].astype(
                    np.int64)
            plan = plan_tiers(n_owned, n_bucket, tile, pad_partitions_to=D)
            specs = tuple((_round_up(len(ids), D), C1, C2)
                          for ids, C1, C2 in plan)
            if D > 1:
                gid, send_base, dest_base, cap, layout, crossing = \
                    _exchange_plan(counts, plan, specs, D)
            else:
                part_tier = np.full(P + 1, -1, np.int32)
                part_local = np.zeros(P + 1, np.int32)
                for t, (ids, _, _) in enumerate(plan):
                    part_tier[ids] = t
                    part_local[ids] = np.arange(len(ids), dtype=np.int32)
                o_starts = np.zeros(P + 1, np.int32)
                np.cumsum(n_owned, out=o_starts[1:])
                b_starts = np.zeros(P + 1, np.int32)
                np.cumsum(n_bucket, out=b_starts[1:])
        stats.shuffle_index_impl = ("jnp" if D > 1 or _use_jnp_indices()
                                    else "host")
        if D > 1:
            row_bytes = codec.device_bytes_per_item(d)
            with tr.span("shuffle.exchange", cat="stage",
                         counters={"exchange_bytes": crossing * row_bytes,
                                   "exchange_rows": crossing}):
                scattered = jax.block_until_ready(_exchange_jit(
                    stream.payloads, stream.keys, stream.dest_eff, stream.src,
                    stream.skey, gid, send_base, dest_base, mesh=mesh,
                    cap=cap, layout=layout))
            stats.exchange_rows += crossing
            stats.exchange_bytes += crossing * row_bytes
        else:
            with tr.span("shuffle.scatter", cat="stage"):
                if _use_jnp_indices():
                    scattered = _scatter_tiers_jit(
                        m.payloads, m.keys, m.dest_eff, m.src, m.skey,
                        jnp.asarray(o_starts), jnp.asarray(b_starts),
                        jnp.asarray(part_tier), jnp.asarray(part_local),
                        specs=specs)
                else:
                    src_h = np.asarray(m.src)
                    live = dest_h < P     # drop non-replicated border slots
                    if not live.all():    # before sorting: fewer copies =
                        dest_h, src_h = dest_h[live], src_h[live]   # less sort
                    scattered = _scatter_tiers_host(
                        m.payloads, keys_h, dest_h, src_h,
                        None if m.skey is None else np.asarray(m.skey),
                        o_starts, b_starts, part_tier, part_local, specs)
                scattered = jax.block_until_ready(scattered)
        with tr.span("shuffle.tiers", cat="stage"):
            tiers = []
            shard_pad = np.zeros(D, np.float64)
            shard_real = np.zeros(D, np.float64)
            for ((ids, C1, C2), (Pt, _, _), (own, bkt)) in zip(
                    plan, specs, scattered):
                no_t = np.zeros(Pt, np.int32)
                nb_t = np.zeros(Pt, np.int32)
                no_t[:len(ids)] = n_owned[ids]
                nb_t[:len(ids)] = n_bucket[ids]
                tiers.append(TierData(ids, own, bkt, _put_counts(no_t, mesh),
                                      _put_counts(nb_t, mesh), C1=C1, C2=C2,
                                      Pt=Pt))
                shard_real += (no_t.astype(np.int64) * nb_t).reshape(
                    D, Pt // D).sum(axis=1)
                shard_pad += float(Pt // D) * C1 * C2
            sd = DeviceShuffledData(tiers, n_owned, n_bucket)
        n_shuffled = int(n_bucket.sum())
        wire = n_shuffled * codec.device_bytes_per_item(d)
        t1 = time.perf_counter()
    stats.shuffle_wall_s += t1 - t0
    stats.shuffle_wire_bytes += wire
    stats.shuffle_raw_bytes += 4 * n_shuffled * d
    stats.n_items += n_rows
    stats.n_partitions = P
    stats.codec = codec.name
    stats.engine = "device"
    stats.n_shards = D
    return ResidentCatalog(partitioner, codec, tile, pad_value, sd, P,
                           mesh=mesh, shard_pad=shard_pad,
                           shard_real=shard_real, n_rows=n_rows, d=d)


def _blocked_stream(blocks, mesh, P: int) -> MappedSplit:
    """Per-chip map outputs as one stream of ``[D * length, ...]`` arrays
    sharded over ``data``, each chip's rows and entries padded to a common
    length (padding rows and entries carry id ``P``: nobody's); ``src``
    stays chip-local. The sort key comes along, sharded like ``keys``
    (padding rows 0), so ``_exchange_jit`` can order each partition by it;
    ``None`` where the partitioner has none."""
    m = next(b for b in blocks if b is not None)
    n = max(max((b.n_rows for b in blocks if b is not None), default=0), 1)
    e = max(max((b.dest_eff.shape[0] for b in blocks if b is not None),
                default=0), 1)

    def rows(get, length, fill):
        return _sharded_rows([None if b is None else get(b) for b in blocks],
                             mesh, length, fill, get(m))

    return MappedSplit(
        payloads=tuple(rows(lambda b, k=k: b.payloads[k], n, 0)
                       for k in range(len(m.payloads))),
        keys=rows(lambda b: b.keys, n, P),
        dest_eff=rows(lambda b: b.dest_eff, e, P),
        src=rows(lambda b: b.src, e, 0),
        skey=None if m.skey is None else rows(lambda b: b.skey, n, 0),
        n_rows=sum(b.n_rows for b in blocks if b is not None),
        d=m.d)


def _put_counts(x: np.ndarray, mesh):
    """A tier's per-partition counts, sharded like its rows."""
    if _data_axis_size(mesh) > 1:
        return jax.device_put(x, NamedSharding(mesh, _ROW))
    return jnp.asarray(x)


def shuffle_once(partitioner: Partitioner, items, *, codec="identity",
                 tile: int = 256, pad_value: float = 0.0, mesh=None,
                 stats: StageStats = None) -> ResidentCatalog:
    """Load + map + shuffle a catalog ONCE into device-resident tiered
    wire-dtype partitions. The returned handle's ``run(jobs)`` serves any
    batch of signature-compatible jobs as a pure fused reduce — the
    shuffle-then-reduce decomposition that ``run_jobs`` executes per call
    and the MR query service amortizes across requests. The shuffle cost
    lands in ``stats`` (also kept as ``ResidentCatalog.load_stats``)."""
    check_positive_int("tile", tile, 256)
    codec = get_codec(codec)
    if stats is None:
        stats = StageStats(job="shuffle_once")
    P = int(partitioner.n_partitions(
        items if isinstance(items, jax.Array) else np.asarray(items)))
    meter = get_meter()
    mtok = meter.begin()
    t0 = time.perf_counter()
    m = map_stream(partitioner, codec, items, P, mesh)
    stats.map_wall_s += time.perf_counter() - t0
    stats.map_bytes += sum(b.nbytes_in for b in
                           (m if isinstance(m, list) else [m]) if b)
    cat = _shuffle_mapped(partitioner, codec, tile, pad_value, m, P, stats,
                          mesh)
    meter.attribute(mtok, stats)
    cat.load_stats = stats
    return cat


def shuffle_reduce_device(jobs, m: MappedSplit, P: int, stats: StageStats,
                          mesh=None):
    """Shuffle + reduce one mapped stream (a single split, or the
    ``concat_mapped`` accumulation of many): count, tier, argsort-bucket,
    scatter in wire dtype, then the tiered masked reduce — sharded over the
    mesh's ``data`` axis with a psum combine when one is given. Decomposed
    as ``_shuffle_mapped`` (-> ``ResidentCatalog``) followed by
    ``ResidentCatalog.reduce_totals``, the same two halves the query
    service runs at catalog-load and per-request time.

    Wall/byte stats ACCUMULATE (``+=``) so streaming runs can call this per
    split; ratio-style fields (``reduce_padded_ratio``/``shard_padded_ratio``)
    are left to the caller, which receives the per-call padded/real cell
    vectors. -> (per-job totals, DeviceShuffledData, shard_pad, shard_real).

    Lane-safety: this call (and ``host_shuffle_reduce``/``map_split_device``)
    keeps NO shared mutable state beyond ``stats`` — the module-level
    jit/shard_map caches are ``lru_cache`` (thread-safe) and everything else
    is local — so concurrent lanes (``executor.LanePool``) may run it on
    independent splits simultaneously, each passing its own private
    ``StageStats`` and merging at commit.
    """
    j0 = jobs[0]
    cat = _shuffle_mapped(j0.partitioner, get_codec(j0.codec), j0.tile,
                          j0.reducer.pad_value, m, P, stats, mesh)
    totals = cat.reduce_totals(tuple(j.reducer for j in jobs), stats)
    return totals, cat.sd, cat.shard_pad, cat.shard_real


@dataclasses.dataclass
class StreamSummary:
    """Aggregate post-shuffle state of a streaming run — what
    ``Reducer.finalize`` sees instead of a materialized ``ShuffledData``.
    ``n_owned``/``n_bucket`` are per-partition counts SUMMED over splits (or
    stitched over partition ranges), so count-based corrections (self-pair
    removal etc.) work unchanged."""

    n_owned: np.ndarray        # [P] int64
    n_bucket: np.ndarray       # [P] int64
    pair_cells: float = 0.0
    owned_cells: float = 0.0
    real_pair_cells: float = 0.0

    @property
    def padded_ratio(self) -> float:
        return (self.pair_cells / self.real_pair_cells
                if self.real_pair_cells else 1.0)


def shuffle_reduce_device_streamed(jobs, ranges, P: int, stats: StageStats,
                                   mesh=None):
    """Shuffle + reduce an ENTRY STREAM of partition ranges — the external
    shuffle's read-back path. ``ranges`` yields ``(lo, hi, m)`` records
    covering disjoint ``[lo, hi)`` slices of the global partition space,
    where ``m`` is a ``MappedSplit`` whose ids are RANGE-LOCAL: keys in
    ``[0, hi-lo)`` for rows the range owns (``hi-lo`` marks payload-only
    border rows carried for bucket entries), ``dest_eff`` in ``[0, hi-lo]``.

    Each range runs the ordinary ``shuffle_reduce_device`` with
    ``P = hi - lo`` — peak resident wire bytes are one range's, not the
    catalog's — and per-job totals tree-add across ranges (disjoint owned
    partitions + commutative integer sums, the same contract that makes
    ``concat_mapped`` order-independent). Per-partition counts stitch into
    global ``[P]`` vectors so finalize corrections see the monolithic view.

    -> (per-job totals, StreamSummary over all ranges, shard_pad,
    shard_real) — the ``shuffle_reduce_device`` return shape with the
    summary standing in for ``DeviceShuffledData``.
    """
    totals = None
    n_owned = np.zeros(P, np.int64)
    n_bucket = np.zeros(P, np.int64)
    pair_pad = pair_real = owned_cells = 0.0
    shard_pad = shard_real = None
    for lo, hi, m in ranges:
        t, sd, sp, sr = shuffle_reduce_device(jobs, m, hi - lo, stats, mesh)
        totals = t if totals is None else tuple(
            jax.tree.map(jnp.add, a, b) for a, b in zip(totals, t))
        n_owned[lo:hi] += sd.n_owned
        n_bucket[lo:hi] += sd.n_bucket
        pair_pad += sd.pair_cells
        pair_real += sd.real_pair_cells
        owned_cells += sd.owned_cells
        if shard_pad is None:
            shard_pad = np.asarray(sp, np.float64).copy()
            shard_real = np.asarray(sr, np.float64).copy()
        else:
            shard_pad += sp
            shard_real += sr
    if totals is None:
        raise ValueError("shuffle_reduce_device_streamed: empty range "
                         "stream — the caller must supply at least one "
                         "range (an all-empty spill still reads one)")
    stats.n_partitions = P
    summary = StreamSummary(n_owned, n_bucket, pair_cells=pair_pad,
                            owned_cells=owned_cells,
                            real_pair_cells=pair_real)
    return totals, summary, shard_pad, shard_real


def host_shuffle_reduce(jobs, items, stats: StageStats, mesh=None):
    """The host engine's shuffle + reduce for one item stream (numpy shuffle
    + ``lax.map`` reduce, sharded over the mesh's ``data`` axis when given)
    — the oracle twin of ``shuffle_reduce_device`` with the same accumulate
    (``+=``) stats contract and return shape.
    -> (per-job totals, ShuffledData, shard_pad, shard_real)."""
    j0 = jobs[0]
    codec = get_codec(j0.codec)
    D = _data_axis_size(mesh)
    local = StageStats()
    sd = shuffle_stage(items, j0.partitioner, codec, tile=j0.tile,
                       pad_partitions_to=D,
                       pad_value=j0.reducer.pad_value, stats=local)
    stats.map_wall_s += local.map_wall_s
    stats.map_bytes += local.map_bytes
    stats.shuffle_wall_s += local.shuffle_wall_s
    stats.shuffle_wire_bytes += local.shuffle_wire_bytes
    stats.shuffle_raw_bytes += local.shuffle_raw_bytes
    stats.n_items += local.n_items
    stats.n_partitions = local.n_partitions
    stats.codec = local.codec
    stats.engine = "host"
    stats.shuffle_index_impl = local.shuffle_index_impl
    stats.n_shards = D
    q = sd.owned.shape[0] // D
    cells = (sd.n_owned.astype(np.float64)
             * sd.n_bucket).reshape(D, q).sum(axis=1)
    pad_cells = float(q) * sd.owned.shape[1] * sd.bucket.shape[1]
    with get_tracer().span("reduce", cat="stage", engine="host"):
        t0 = time.perf_counter()
        totals = jax.block_until_ready(
            reduce_stage([j.reducer for j in jobs], sd, mesh))
        t1 = time.perf_counter()
    stats.reduce_wall_s += t1 - t0
    stats.reduce_bytes += sd.owned.nbytes + sd.bucket.nbytes
    stats.reduce_flops += float(sum(j.reducer.flops(sd) for j in jobs))
    return totals, sd, np.full(D, pad_cells), np.asarray(cells, np.float64)


# ---------------------------------------------------------------------------
# Entry points (one-split special case of the streaming executor)
# ---------------------------------------------------------------------------

def shuffle_signature(job: MapReduceJob) -> tuple:
    """The (partitioner, codec name, tile, pad_value) key of a job's
    map+shuffle stages. Jobs sharing it can batch over ONE shuffle
    (``run_jobs``) or reduce against one ``ResidentCatalog``."""
    return (job.partitioner, get_codec(job.codec).name, job.tile,
            job.reducer.pad_value)


def group_batch_compatible(jobs) -> "list[list[MapReduceJob]]":
    """Partition ``jobs`` into the fewest groups that each share one shuffle
    signature (order preserved within a group) — how the MR query service
    coalesces an admission window's requests into fused reduce passes."""
    groups: list[list[MapReduceJob]] = []
    sigs: list[tuple] = []
    for j in jobs:
        sig = shuffle_signature(j)
        for g, s in zip(groups, sigs):
            if s == sig:
                g.append(j)
                break
        else:
            groups.append([j])
            sigs.append(sig)
    return groups


def validate_batch(jobs) -> None:
    """Batched jobs must share one shuffle (partitioner/codec/tile/pad)."""
    j0 = jobs[0]
    c0 = get_codec(j0.codec)
    for j in jobs[1:]:
        diffs = [k for k, a, b in [
            ("partitioner", j.partitioner, j0.partitioner),
            ("codec", get_codec(j.codec).name, c0.name),
            ("tile", j.tile, j0.tile),
            ("pad_value", j.reducer.pad_value, j0.reducer.pad_value),
        ] if a != b]
        if diffs:
            raise ValueError(
                f"batched jobs must share one shuffle: {j.name!r} differs "
                f"from {j0.name!r} in {', '.join(diffs)}")


def run_jobs(jobs, items, *, mesh=None, engine: str = "auto",
             split_rows=None) -> list[JobResult]:
    """Execute several jobs that share partitioner/codec/tile through ONE
    map+shuffle and one fused reduce pass (e.g. Neighbor Searching and
    Neighbor Statistics over the same catalog cost a single data pass).

    This is the ONE-SPLIT special case of the streaming executor
    (``mapreduce/executor.py``): the whole catalog is a single
    ``ArraySplits`` split, no combiner, no prefetch — the identical
    map/shuffle/reduce code path the executor runs per split, so streaming
    over N splits is bit-identical to this for exact codecs.

    ``engine``: ``"device"`` (wire-dtype shuffle + tiered masked batched
    reduce; under a data-axis ``mesh`` the tiers shard over ``data`` and
    tier partials combine with a psum), ``"host"`` (numpy shuffle +
    ``lax.map`` reduce; the oracle-parity path, on or off mesh), or
    ``"auto"`` (always device — both engines shard over any data-axis
    mesh). -> one JobResult per job, sharing a single StageStats.

    ``split_rows``: ``None`` (default) runs the whole catalog as one split;
    a positive int streams it in row chunks of that size. Streaming is
    bit-identical to monolithic for exact codecs, so this only changes
    shapes, never results."""
    from repro.data.pipeline import ArraySplits
    from repro.mapreduce.executor import run_jobs_streaming
    if split_rows is not None:
        check_positive_int("split_rows", split_rows, 4096)
    rows = np.asarray(items)
    n_splits = (1 if split_rows is None
                else max(1, -(-len(rows) // int(split_rows))))
    return run_jobs_streaming(jobs, ArraySplits(items, n_splits=n_splits),
                              mesh=mesh, engine=engine, combiner=None,
                              prefetch=0)


def run_job(job: MapReduceJob, items, *, mesh=None, engine: str = "auto",
            split_rows=None) -> JobResult:
    """Execute one job end-to-end. -> JobResult(output, stats)."""
    return run_jobs([job], items, mesh=mesh, engine=engine,
                    split_rows=split_rows)[0]
