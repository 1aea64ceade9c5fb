"""Pallas TPU kernel: weighted segment accumulation over binned items.

The generalized Matrix-PIC scatter (core/matrix_scatter.py stage 2):

    out[v, d] = sum_c  W[v, c] * U[v, c, d]

with V bins of capacity `cap` (gaps carry zero weight). Used for the
embedding-gradient and MoE-combine paths of the LM stack. Grid tiles
(bins x feature) so arbitrarily wide D fits VMEM; the contraction over the
capacity axis runs on the MXU as a batched (1, cap) @ (cap, D_blk) matmul.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.shape_functions import CONTRACTION_PRECISION
from repro.kernels.common import (
    choose_block_cells,
    resolve_interpret,
    vmem_bytes,
)


def _segment_accum_kernel(w_ref, u_ref, o_ref):
    w = w_ref[...][:, None, :]  # (VB, 1, cap): Mosaic needs a non-contracting lhs dim
    u = u_ref[...]  # (VB, cap, DB)
    o_ref[...] = jax.lax.dot_general(
        w,
        u,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=o_ref.dtype,
        precision=CONTRACTION_PRECISION,
    )[:, 0]


def segment_accumulate_pallas(
    w: jax.Array,
    u: jax.Array,
    *,
    block_bins: int | None = None,
    block_d: int = 512,
    interpret: bool | None = None,
    vmem_budget_bytes: int | None = None,
) -> jax.Array:
    """w: (V, cap), u: (V, cap, D) -> (V, D) in u.dtype accumulated fp32."""
    v, cap = w.shape
    d = u.shape[2]
    db = min(block_d, d)
    interpret = resolve_interpret(interpret)
    if block_bins is None:
        # double-buffered in/out blocks; each (1, k) row counted as its own
        # (8, 128) tile also covers the kernel's (VB, 1, k) relayouts
        per_bin = 2 * vmem_bytes((1, cap), (cap, db), (1, db), tiled=not interpret)
        block_bins = choose_block_cells(
            v, per_bin, vmem_budget_bytes=vmem_budget_bytes, interpret=interpret
        )
    vb = min(block_bins, v)

    grid = (pl.cdiv(v, vb), pl.cdiv(d, db))
    out = pl.pallas_call(
        _segment_accum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((vb, cap), lambda i, j: (i, 0)),
            pl.BlockSpec((vb, cap, db), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((vb, db), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((v, d), jnp.float32),
        interpret=interpret,
    )(w, u)
    return out.astype(u.dtype)
