"""Scan-aware HLO cost analysis: validated against an unrolled lowering
(no scan => XLA's own cost_analysis is exact) and on synthetic loops."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import hlo_analysis as H


def _compile_text(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return c, c.as_text()


def test_scan_flops_scale_with_trip_count():
    """flops(scan of L matmuls) must be ~L x flops(1 matmul)."""
    def scanned(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h.sum()

    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    flops = {}
    for L in (2, 8):
        w = jax.ShapeDtypeStruct((L, 128, 128), jnp.float32)
        _, text = _compile_text(scanned, x, w)
        flops[L] = H.analyze(text)["flops_corrected"]
    ratio = flops[8] / flops[2]
    assert 3.0 < ratio < 5.0, ratio  # ~4x (loop-invariant outside parts)


def test_matches_unrolled_ground_truth():
    """Unrolled python loop == XLA exact; scanned + correction must agree
    on dot flops within 20%."""
    L, D = 6, 128

    def unrolled(x, w):
        h = x
        for i in range(L):
            h = jnp.tanh(h @ w[i])
        return h.sum()

    def scanned(x, w):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h.sum()

    x = jax.ShapeDtypeStruct((8, D), jnp.float32)
    w = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    cu, _ = _compile_text(unrolled, x, w)
    xla_flops = cu.cost_analysis()["flops"]
    _, text_s = _compile_text(scanned, x, w)
    ours = H.analyze(text_s)["flops_corrected"]
    assert abs(ours - xla_flops) / xla_flops < 0.2, (ours, xla_flops)


def test_collectives_inside_loops_are_multiplied():
    """An all-reduce inside a scan body counts trip_count times."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device (run under dryrun env)")


def test_dynamic_slice_counts_slice_not_operand():
    def f(big, idx):
        def body(c, i):
            sl = jax.lax.dynamic_slice(big, (i * 8, 0), (8, 64))
            return c + sl.sum(), None
        out, _ = jax.lax.scan(body, 0.0, idx)
        return out

    big = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
    idx = jax.ShapeDtypeStruct((16,), jnp.int32)
    _, text = _compile_text(f, big, idx)
    bytes_ = H.analyze(text)["bytes_corrected"]
    # 16 iterations x ~2x slice (8*64*4=2 KiB) plus small overheads;
    # full-operand counting would give >= 16 x 256 KiB = 4 MiB.
    assert bytes_ < 1.5e6, bytes_


def test_parse_tuple_types_with_index_comments():
    text = """
HloModule m

%body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[4,4]{1,0} get-tuple-element(%p), index=1
  %y = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[4,4]{1,0}) tuple(%i2, %y)
}

%cond (p: (s32[], f32[4,4])) -> pred[] {
  %p.1 = (s32[], f32[4,4]{1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}

ENTRY %main (x: f32[4,4]) -> f32[4,4] {
  %x.2 = f32[4,4]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[4,4]{1,0}) tuple(%z, %x.2)
  %w = (s32[], /*index=1*/f32[4,4]{1,0}) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %r = f32[4,4]{1,0} get-tuple-element(%w), index=1
}
"""
    r = H.analyze(text)
    # 7 trips x (2 * 4*4*4 = 128 flops per dot)
    assert abs(r["flops_corrected"] - 7 * 128) < 7 * 16, r["flops_corrected"]
