"""HLO analyzer units: dot FLOPs, loop multipliers, collective classification."""
import numpy as np

from repro.core.hlo_analysis import analyze_hlo, _parse_groups, _shape_bytes

HLO = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %w = f32[8,8] constant({...})
  %d = f32[8,8] dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8] parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%zero, %a)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  %ar = f32[8,8] all-reduce(%a), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%cond
  %ag = f32[16,8] all-gather(%a), replica_groups=[2,4]<=[8], dimensions={0}
  ROOT %r = f32[8,8] get-tuple-element(%w), index=1
}
"""


def test_loop_multiplied_dot_flops():
    a = analyze_hlo(HLO)
    # 7 iterations x 2*8*8*8 flops
    assert a.flops == 7 * 2 * 8 * 8 * 8


def test_collective_wire_bytes():
    a = analyze_hlo(HLO)
    ops = {c.op: c for c in a.collectives}
    # all-reduce: 2 * 256B * 3/4
    assert abs(ops["all-reduce"].wire_bytes - 2 * 256 * 0.75) < 1e-6
    # all-gather: output 512B * 3/4
    assert abs(ops["all-gather"].wire_bytes - 512 * 0.75) < 1e-6


def test_cross_pod_classification():
    a = analyze_hlo(HLO, pod_size=4)
    ops = {c.op: c for c in a.collectives}
    assert not ops["all-reduce"].cross_pod        # {0..3} within pod 0
    assert not ops["all-gather"].cross_pod        # iota [2,4]<=[8]: group0={0..3}
    # transposed iota spreads a group across pods: [4,2]<=[2,4]T(1,0) -> {0,4},...
    line = ("%x = f32[8] all-gather(%a), "
            "replica_groups=[4,2]<=[2,4]T(1,0), dimensions={0}")
    a2 = analyze_hlo("ENTRY %m (a: f32[8]) -> f32[8] {\n  " + line +
                     "\n  ROOT %r = f32[8] add(%x, %x)\n}\n", pod_size=4)
    assert a2.collectives and a2.collectives[0].cross_pod


def test_iota_group_parse():
    gsize, cross = _parse_groups(
        "x = f32[4] all-gather(%y), replica_groups=[2,4]<=[8], dimensions={0}",
        pod_size=4)
    assert gsize == 4
    ids = np.arange(8).reshape(2, 4)
    assert cross == (len({int(i) // 4 for i in ids[0]}) > 1)


def test_shape_bytes():
    assert _shape_bytes("f32[2,3]") == 24
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("(s32[], f32[4,4]{1,0})") == 4 + 64
    assert _shape_bytes("s8[100]") == 100


# ---------------------------------------------------------------------------
# census over real mapreduce stage callables
# ---------------------------------------------------------------------------

def test_census_counts_dot_flops_in_reduce_stage():
    import jax
    import jax.numpy as jnp

    P, C1, C2, d = 4, 64, 96, 3
    a = jax.ShapeDtypeStruct((P, C1, d), jnp.float32)
    b = jax.ShapeDtypeStruct((P, C2, d), jnp.float32)
    fn = lambda x, y: jnp.einsum("pcd,ped->pce", x, y)  # noqa: E731
    cen = analyze_hlo(jax.jit(fn).lower(a, b).compile().as_text())
    # one batched dot: 2 * P * C1 * C2 * d FLOPs, reads/writes nonzero bytes
    assert cen.flops == 2.0 * P * C1 * C2 * d
    assert cen.hbm_bytes > 0


def test_census_blocked_chunk_elementwise_flops():
    import jax
    import jax.numpy as jnp
    from repro.kernels.zones_pairs.blocked import _count_chunk

    tm, tn, b0 = 32, 32, 64
    rng = np.random.default_rng(0)
    a = rng.standard_normal((b0, tm, 3)).astype(np.float32)
    b = rng.standard_normal((b0, tn, 3)).astype(np.float32)
    a /= np.linalg.norm(a, axis=-1, keepdims=True)
    b /= np.linalg.norm(b, axis=-1, keepdims=True)
    args = (jnp.asarray(a), jnp.asarray(b), jnp.full(b0, tm, jnp.int32),
            jnp.full(b0, tn, jnp.int32), jnp.float32(0.99))
    cen = analyze_hlo(jax.jit(_count_chunk).lower(*args).compile().as_text())
    # the pair kernel is an unrolled broadcast-multiply-add (the bit-parity
    # contract forbids a real dot), so its work shows up as ELEMENTWISE
    # flops inside fusions — zero dot flops is load-bearing, not a gap
    assert cen.flops == 0.0
    assert cen.ew_flops > 0.0
    assert cen.hbm_bytes > 0.0
    assert cen.summary()["ew_flops_per_device"] == cen.ew_flops
