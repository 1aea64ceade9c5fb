"""Unit tests for the shared fixed-point compression core
(`repro.distributed.compression`): quantize/dequantize error bounds, the
migration payload packers, and the error-feedback residual identity of the
int8 gradient all-reduce. The multi-device convergence check of the
compressed DP path lives in the slow lane (tests/dist_lm_check.py), and
the compressed-migration physics parity in tests/dist_comm_check.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.compression import (
    MIG_ROW_BYTES_COMPRESSED,
    MIG_ROW_BYTES_EXACT,
    POS_MARGIN,
    compressed_psum_grads,
    dequantize_fixed,
    exact_pmean_grads,
    pack_momenta,
    pack_positions,
    quantize_fixed,
    unpack_momenta,
    unpack_positions,
    zeros_like_residual,
)


def test_fixed_point_round_trip_bound():
    """Reconstruction error of the shared core is bounded by scale/2 for
    every in-range value."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(-1.0, 1.0, size=(512,)), jnp.float32)
    scale = 2.0 / 255.0
    q = quantize_fixed(x, scale, qmin=-127, qmax=127, dtype=jnp.int8)
    err = np.abs(np.asarray(dequantize_fixed(q, scale)) - np.asarray(x))
    assert err.max() <= scale / 2 + 1e-7


def test_fixed_point_clips_out_of_range():
    x = jnp.asarray([-10.0, 10.0], jnp.float32)
    q = quantize_fixed(x, 0.01, qmin=-127, qmax=127, dtype=jnp.int8)
    np.testing.assert_array_equal(np.asarray(q), [-127, 127])


def test_pack_positions_round_trip_bound():
    """Positions anywhere in the headroom band [-POS_MARGIN, ext+POS_MARGIN)
    round-trip within the documented tolerance (ext + 2*margin)/2^16."""
    shape = (4, 8, 32)
    rng = np.random.default_rng(1)
    pos = np.stack(
        [rng.uniform(-POS_MARGIN, s + POS_MARGIN, size=4096) for s in shape], axis=1
    ).astype(np.float32)
    out = np.asarray(unpack_positions(pack_positions(jnp.asarray(pos), shape), shape))
    tol = (np.asarray(shape, np.float64) + 2 * POS_MARGIN) / 2**16
    assert (np.abs(out - pos) <= tol[None, :] / 2 + 1e-6).all()


def test_pack_positions_preserves_out_of_range():
    """An out-of-range coordinate (a migrant's *other* dim, up to one CFL
    cell outside the block) must stay out of range after the round trip —
    clipping into [0, ext) would silently cancel its next migration."""
    shape = (8, 8, 8)
    pos = jnp.asarray([[-0.7, 4.0, 8.9], [8.5, -0.2, 3.0]], jnp.float32)
    out = np.asarray(unpack_positions(pack_positions(pos, shape), shape))
    assert out[0, 0] < 0.0 and out[0, 2] > 8.0
    assert out[1, 0] > 8.0 and out[1, 1] < 0.0


def test_pack_momenta_bf16_relative_error():
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(0.0, 3.0, size=(1024, 3)), jnp.float32)
    out = np.asarray(unpack_momenta(pack_momenta(u)))
    rel = np.abs(out - np.asarray(u)) / np.maximum(np.abs(np.asarray(u)), 1e-6)
    assert rel.max() <= 2.0 ** -8  # bf16 has 8 significand bits

def test_payload_row_bytes():
    assert MIG_ROW_BYTES_EXACT == 28      # 3x f32 pos + 3x f32 u + f32 w
    assert MIG_ROW_BYTES_COMPRESSED == 16  # 3x u16 pos + 3x bf16 u + f32 w


def _psum_one(grads, residuals, compress: bool):
    """Run one (possibly compressed) gradient all-reduce on a 1-device mesh
    (psum/pmax degenerate to identity; the quantize/residual algebra is
    exercised unchanged)."""
    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,) * 1)

    def body(g, r):
        if compress:
            return compressed_psum_grads(g, r, "data")
        return exact_pmean_grads(g, "data"), r

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False
    )(grads, residuals)


def test_error_feedback_residual_identity():
    """residual' = g' - dequant(quant(g')) exactly, and the reduced value
    plus the new residual reconstructs the error-fed gradient."""
    rng = np.random.default_rng(3)
    g = {"w": jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)}
    res = zeros_like_residual(g)
    out, new_res = _psum_one(g, res, compress=True)
    # on one shard the reduced value is exactly dequant(quant(g)), so
    # out + residual' == g to float32 round-off
    np.testing.assert_allclose(
        np.asarray(out["w"]) + np.asarray(new_res["w"]), np.asarray(g["w"]),
        rtol=0, atol=1e-6,
    )
    assert np.abs(np.asarray(new_res["w"])).max() > 0  # quantization did err


def test_error_feedback_error_does_not_accumulate():
    """Feeding the residual forward keeps the accumulated reduced sum within
    one quantization step of the accumulated true sum (the EF property), vs.
    a drifting bias when the residual is discarded."""
    rng = np.random.default_rng(4)
    g = {"w": jnp.asarray(rng.normal(size=(8, 8)) * 1e-3 + 5e-3, jnp.float32)}
    res = zeros_like_residual(g)
    acc = np.zeros((8, 8), np.float64)
    for _ in range(50):
        out, res = _psum_one(g, res, compress=True)
        acc += np.asarray(out["w"], np.float64)
    true = 50 * np.asarray(g["w"], np.float64)
    scale = float(np.abs(np.asarray(g["w"])).max()) / 127.0
    assert np.abs(acc - true).max() <= 2 * scale  # bounded, not O(steps)


def test_compressed_matches_exact_on_uniform_grads():
    """With identical per-shard gradients the compressed mean equals the
    exact mean to quantization tolerance."""
    g = {"w": jnp.full((4, 4), 0.5, jnp.float32)}
    exact, _ = _psum_one(g, zeros_like_residual(g), compress=False)
    comp, _ = _psum_one(g, zeros_like_residual(g), compress=True)
    np.testing.assert_allclose(
        np.asarray(comp["w"]), np.asarray(exact["w"]), rtol=0, atol=0.5 / 127.0
    )
