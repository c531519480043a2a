"""Compile the TPU reduce kernels for a described v5e, with no chip attached.

Interpret-mode tests cannot see what the Pallas TPU lowering refuses
(unaligned blocks, rank-1 VMEM blocks, VMEM overruns) or whether a sharded
reduce keeps its kernel and all-reduce. These compile the masked pair
kernels at engine shapes (``tile=256``, capacities up to 8192) and one
4-device sharded count + ``psum`` for a described ``v5e:2x2``. Nothing
runs, so they say nothing about results or times.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.zones_pairs.kernel import (_hist_call,
                                              pair_count_masked_pallas,
                                              pair_hist_masked_pallas)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel was not lowered"
    return compiled, text


@pytest.mark.parametrize("P_,C1,C2,nbins", [
    (16, 1024, 2048, 0),        # count (nbins 0 = the count kernel)
    (4, 2048, 8192, 0),
    (16, 1024, 2048, 4),        # the service mix's histogram
    (4, 8192, 8192, 60),        # the default 60-edge statistics job
])
def test_masked_kernels_compile_for_v5e(topo, no_persistent_cache, P_, C1,
                                        C2, nbins):
    one = SingleDeviceSharding(topo.devices[0])
    a = jax.ShapeDtypeStruct((P_, C1, 3), jnp.float32, sharding=one)
    b = jax.ShapeDtypeStruct((P_, C2, 3), jnp.float32, sharding=one)
    n = jax.ShapeDtypeStruct((P_,), jnp.int32, sharding=one)
    if nbins:
        e = jax.ShapeDtypeStruct((nbins,), jnp.float32, sharding=one)
        compiled, _ = _compile(pair_hist_masked_pallas, a, b, n, n, e)
        assert compiled.out_info.shape == (nbins,)
    else:
        compiled, _ = _compile(
            lambda a, b, na, nb: pair_count_masked_pallas(
                a, b, na, nb, float(np.cos(0.02))), a, b, n, n)
        assert compiled.out_info.shape == ()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 << 30


def test_pair_kernel_keeps_its_profile_name(topo, no_persistent_cache):
    """A device profile finds the pair kernel by the name of its custom
    call: the program each tier dispatches names it ``tpu_custom_call``."""
    one = SingleDeviceSharding(topo.devices[0])
    P_, C1, C2, nbins = 4, 1024, 2048, 16
    n = jax.ShapeDtypeStruct((P_,), jnp.int32, sharding=one)
    text = _hist_call(P_, C1, C2, 3, nbins, 256, 256, False).lower(
        n, n, jax.ShapeDtypeStruct((nbins,), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((P_, C1, 3), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((P_, 3, C2), jnp.float32, sharding=one),
    ).compile().as_text()
    assert re.search(r"%tpu_custom_call[.\d]* = [^\n]*custom-call", text)


def test_sharded_count_psum_compiles_for_v5e_2x2(topo, no_persistent_cache):
    from repro.core.compat import shard_map
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("data",))
    rows = NamedSharding(mesh, P("data"))
    cmin = float(np.cos(0.02))

    def body(a, b, na, nb):
        return jax.lax.psum(pair_count_masked_pallas(a, b, na, nb, cmin),
                            "data")

    fn = shard_map(body, mesh=mesh, in_specs=(P("data"),) * 4,
                   out_specs=P(), axis_names=frozenset({"data"}))
    Pt, C1, C2 = 16, 1024, 4096
    _, text = _compile(
        fn,
        jax.ShapeDtypeStruct((Pt, C1, 3), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((Pt, C2, 3), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((Pt,), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((Pt,), jnp.int32, sharding=rows))
    assert "all-reduce" in text
