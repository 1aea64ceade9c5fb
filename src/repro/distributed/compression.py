"""Quantized payload compression for the distributed collectives.

Two consumers share the fixed-point quantize–dequantize core here:

1. **Error-feedback int8 gradient compression** for the data-parallel
   all-reduce (1-bit-Adam / EF-SGD family):

     1. g' = g + residual                  (error feedback)
     2. scale = pmax(|g'|) / 127           (shared scale across the DP axis)
     3. q = round(g'/scale) in int8        (4x less ICI traffic than fp32)
     4. G = psum(q) * scale / n_shards     (integer all-reduce)
     5. residual' = g' - dequant(q)        (compression error carried forward)

   Exposed as `compressed_psum_grads` for use inside a shard_map'd DP train
   step. With compression disabled it degenerates to a plain psum (the test
   compares convergence of both paths).

2. **Compressed migration payloads** for the PIC particle exchange
   (`pic.distributed.migrate_axis` with ``comm.compress_migration``):
   positions are shard-relative after the migration coordinate shift, so
   they quantize to fixed-point uint16 over the local block extent (plus a
   ±`POS_MARGIN`-cell headroom band: a particle leaving along x may still
   be up to one CFL-bounded cell out of range along y, and clipping that
   coordinate into range would silently cancel its next migration).
   Momenta round-trip through bfloat16; weights stay exact float32 so the
   total charge is conserved exactly. Documented tolerance per position
   component: ``(extent + 2*POS_MARGIN) / 2**16`` grid cells (the uint16
   step), i.e. < 1.1e-3 cells for local extents up to 64.

   Payload accounting (per buffered particle row, the `BENCH_comm` bytes):
   exact 28 B (3x f32 pos + 3x f32 u + f32 w); compressed 16 B
   (3x uint16 pos + 3x bf16 u + f32 w).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# Out-of-range headroom for position quantization, in grid cells: CFL bounds
# a particle's per-step motion below one cell, so any coordinate of a
# migrating particle lies in [-POS_MARGIN, extent + POS_MARGIN).
POS_MARGIN = 2.0

# Payload bytes per buffered migration row (pos + u + w), both modes.
MIG_ROW_BYTES_EXACT = 3 * 4 + 3 * 4 + 4
MIG_ROW_BYTES_COMPRESSED = 3 * 2 + 3 * 2 + 4


# ---------------------------------------------------------------------------
# shared fixed-point core
# ---------------------------------------------------------------------------

def quantize_fixed(x, scale, *, qmin: int, qmax: int, dtype, zero=0.0):
    """x -> round((x - zero)/scale) clipped into [qmin, qmax] as `dtype`.

    `scale`/`zero` may be scalars or broadcastable arrays (per-dim position
    scales). The reconstruction `dequantize_fixed` is exact to scale/2."""
    q = jnp.round((x - zero) / scale)
    return jnp.clip(q, qmin, qmax).astype(dtype)


def dequantize_fixed(q, scale, *, zero=0.0, dtype=jnp.float32):
    return q.astype(dtype) * scale + zero


# ---------------------------------------------------------------------------
# error-feedback int8 gradient all-reduce
# ---------------------------------------------------------------------------

def zeros_like_residual(grads):
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _compress_one(g, r, axis_name):
    g32 = g.astype(jnp.float32) + r
    amax = lax.pmax(jnp.max(jnp.abs(g32)), axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = quantize_fixed(g32, scale, qmin=-127, qmax=127, dtype=jnp.int8)
    new_r = g32 - dequantize_fixed(q, scale)
    n = jax.lax.axis_size(axis_name)
    summed = lax.psum(q.astype(jnp.int32), axis_name).astype(jnp.float32) * scale / n
    return summed.astype(g.dtype), new_r


def compressed_psum_grads(grads, residuals, axis_name: str):
    """Returns (mean-reduced grads, new residuals)."""
    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(residuals)
    out = [_compress_one(g, r, axis_name) for g, r in zip(flat_g, flat_r)]
    return treedef.unflatten([o[0] for o in out]), treedef.unflatten([o[1] for o in out])


def exact_pmean_grads(grads, axis_name: str):
    return jax.tree.map(lambda g: lax.pmean(g, axis_name), grads)


# ---------------------------------------------------------------------------
# migration payload packing (pic.distributed.migrate_axis)
# ---------------------------------------------------------------------------

def _pos_scales(local_shape, dtype=jnp.float32):
    """Per-dim (scale, zero) mapping [-POS_MARGIN, ext + POS_MARGIN) onto
    the uint16 range. Static given the (static) local grid shape."""
    ext = jnp.asarray(local_shape, dtype)
    scale = (ext + 2.0 * POS_MARGIN) / 65536.0
    zero = jnp.full_like(ext, -POS_MARGIN)
    return scale, zero


def pack_positions(pos, local_shape):
    """(cap, 3) shard-relative positions -> uint16 fixed point. Dequantized
    values stay strictly below ext + POS_MARGIN (qmax maps below the range
    top), so out-of-range coordinates survive the round trip and still
    trigger their next migration."""
    scale, zero = _pos_scales(local_shape, pos.dtype)
    return quantize_fixed(pos, scale, zero=zero, qmin=0, qmax=65535, dtype=jnp.uint16)


def unpack_positions(q, local_shape, dtype=jnp.float32):
    scale, zero = _pos_scales(local_shape, dtype)
    return dequantize_fixed(q, scale, zero=zero, dtype=dtype)


def pack_momenta(u):
    return u.astype(jnp.bfloat16)


def unpack_momenta(q, dtype=jnp.float32):
    return q.astype(dtype)
