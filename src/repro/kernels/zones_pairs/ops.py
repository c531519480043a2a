"""Backend selection for the Zones pair reductions.

``pair_count``/``pair_hist`` are the host engine's per-partition oracle: the
plain jnp reference on every backend, so the reference never runs the code
under test.

``pair_count_masked``/``pair_hist_masked`` are the engine="device" batched
reduce: one call covers a whole size tier of partitions, padded rows masked
via n_a/n_b, never via pad-value tricks. On TPU: the Pallas kernels with a
leading partition grid axis. Elsewhere: the z-banded blocked reduce
(``blocked.py``) — same results, tile pairs outside the z band pruned,
fixed-shape chunks so the XLA compile is shared across codecs, radii, and
job shapes. These run eagerly (the blocked path plans its blocks on the
host), NOT under jit.

Traceability: the Pallas variants are pure traced jax and can run inside a
``shard_map`` region (the mesh-sharded device reduce; interpret mode
included), and both tolerate all-padding shards (every n_a/n_b zero — the
``pl.when`` guard / validity mask zero out every tile). The blocked path
CANNOT be traced (host-side block planning); ``masked_uses_pallas``
resolves which one a given ``use_pallas`` setting lands on, so the engine
knows whether the sharded reduce may trace the kernel or must slice shards
eagerly. Interpret mode is reachable only through an explicit
``use_pallas=True`` off the chip (tests, ``md_check.py``).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.zones_pairs.kernel import (pair_count_masked_pallas,
                                              pair_hist_masked_pallas)
from repro.kernels.zones_pairs.ref import pair_count_ref, pair_hist_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


pair_count = jax.jit(pair_count_ref, static_argnames=("exclude_self",))
pair_hist = jax.jit(pair_hist_ref, static_argnames=("exclude_self",))


def masked_uses_pallas(use_pallas: bool | None = None) -> bool:
    """Resolve a ``use_pallas`` setting: True -> traceable Pallas masked
    kernels, False -> the eager-only z-banded blocked engine."""
    return _on_tpu() if use_pallas is None else use_pallas


def pair_count_masked(a, b, n_a, n_b, cos_min, *,
                      use_pallas: bool | None = None):
    if masked_uses_pallas(use_pallas):
        return pair_count_masked_pallas(a, b, n_a, n_b, cos_min,
                                        interpret=not _on_tpu())
    from repro.kernels.zones_pairs.blocked import pair_count_blocked
    return pair_count_blocked(a, b, n_a, n_b, cos_min)


def pair_hist_masked(a, b, n_a, n_b, cos_edges, *,
                     use_pallas: bool | None = None):
    if masked_uses_pallas(use_pallas):
        return pair_hist_masked_pallas(a, b, n_a, n_b, cos_edges,
                                       interpret=not _on_tpu())
    from repro.kernels.zones_pairs.blocked import pair_hist_blocked
    return pair_hist_blocked(a, b, n_a, n_b, cos_edges)
