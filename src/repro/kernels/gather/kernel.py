"""Pallas TPU kernels: binned field gather (inverse of the deposition
kernels).

Per cell, the (Tx, Ty*Tz) node neighbourhood G_c is shared by every particle
in the bin (the locality the GPMA sorter establishes); each particle's value
is

    e[c, p] = sum_m wx[c, p, m] * (sum_n byz[c, p, n] * G[c, m, n])

i.e. one batched matmul (contract the tap product axis on the MXU) plus a
small VPU reduction over the Tx taps.

Two kernels live here.

`bin_gather_pallas` — the single-component contraction with the weight
  operands wx/byz built *outside* the kernel (they round-trip through HBM).
  The ``gather="matrix_unfused"`` + ``backend="pallas"`` comparison route.

`fused_gather_pallas` — the fused six-component megakernel (the dual of
`fused_deposition_pallas`). Per cell-block it:

  (a) loads the step's `BinSlab` offsets ``d:(C, cap, 3)`` — staged ONCE
      per step and shared with the fused deposition — plus one packed
      neighborhood tensor ``g:(C, 6, T, T*T)`` holding all six field
      components (Ex..Bz) on the order's *unified* tap window
      (shape_functions.unified_support), E and B staggers packed together;
  (b) computes the six 1-D shape-weight sets (centered + staggered per
      axis) in-kernel on the VPU — the values of
      `shape_functions.packed_axis_weights`, built with the taps on lanes
      (`lane_axis_weights`) so Mosaic lowers them; off-support taps are
      exactly 0, so the unified window changes nothing but the (shared)
      operand shapes;
  (c) reuses the four distinct wy⊗wz tap products across the component
      pairs that share them and runs the six MXU contractions against the
      packed neighborhoods;
  (d) writes one ``(C, cap, 6)`` per-bin value tile.

The weight and byz operand tensors therefore never exist in HBM — only the
thin (C, cap, 3) slab and the neighborhood tiles stream in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.gather import EB_STAGGERS
from repro.core.shape_functions import CONTRACTION_PRECISION, lane_axis_weights, unified_support
from repro.kernels.common import (
    choose_block_cells,
    resolve_interpret,
    vmem_bytes,
)


def _gather_kernel(wx_ref, byz_ref, g_ref, o_ref):
    wx = wx_ref[...]    # (CB, cap, M)
    byz = byz_ref[...]  # (CB, cap, N)
    g = g_ref[...]      # (CB, M, N)
    # H[c,p,m] = sum_n byz[c,p,n] * G[c,m,n]   (MXU batched matmul)
    h = jax.lax.dot_general(
        byz, g, dimension_numbers=(((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32,
        precision=CONTRACTION_PRECISION,
    )
    # e[c,p] = sum_m wx * H                    (VPU reduction)
    o_ref[...] = jnp.sum(wx * h, axis=-1)


def bin_gather_pallas(
    wx: jax.Array,
    byz: jax.Array,
    g: jax.Array,
    *,
    block_cells: int | None = None,
    interpret: bool | None = None,
    vmem_budget_bytes: int | None = None,
) -> jax.Array:
    """wx: (C, cap, M); byz: (C, cap, N); g: (C, M, N) -> (C, cap) values."""
    c, cap, m = wx.shape
    n = byz.shape[2]
    interpret = resolve_interpret(interpret)
    if block_cells is None:
        # double-buffered in/out blocks plus the live (cap, M) H and wx*H
        tiled = not interpret
        io = vmem_bytes((cap, m), (cap, n), (m, n), (1, cap), tiled=tiled)
        per_cell = 2 * io + vmem_bytes((cap, m), (cap, m), tiled=tiled)
        block_cells = choose_block_cells(
            c, per_cell, vmem_budget_bytes=vmem_budget_bytes, interpret=interpret
        )
    cb = min(block_cells, c)

    grid = (pl.cdiv(c, cb),)
    return pl.pallas_call(
        _gather_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cb, cap, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((cb, cap, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((cb, m, n), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((cb, cap), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((c, cap), jnp.float32),
        interpret=interpret,
    )(wx, byz, g)


# ---------------------------------------------------------------------------
# Fused six-component megakernel
# ---------------------------------------------------------------------------


def _make_fused_gather_kernel(order: int):

    def kernel(d_ref, g_ref, o_ref):
        cb, cap, _ = d_ref.shape

        # (b) six 1-D weight sets on the VPU, one evaluation for all six
        # components (every component is centered or staggered per axis).
        # Taps sit on lanes (shape_functions.lane_axis_weights): the y and
        # z sets are evaluated directly on the flattened (T*T) outer-product
        # axis, so the wy (x) wz products below are elementwise multiplies.
        w = lane_axis_weights(d_ref, order)

        # (c) six MXU contractions sharing the weights; the four distinct
        # wy (x) wz products are built once and reused across the component
        # pairs that share them (Ey/Bz and Ez/By)
        lane = jax.lax.broadcasted_iota(jnp.int32, (cb, cap, 6), 2)
        byz = {}
        out = jnp.zeros((cb, cap, 6), jnp.float32)
        for comp, stagger in enumerate(EB_STAGGERS):
            key = (stagger[1], stagger[2])
            if key not in byz:
                byz[key] = w[(1, stagger[1])] * w[(2, stagger[2])]
            # H[c,p,m] = sum_n byz[c,p,n] * G[c,comp,m,n]   (MXU)
            h = jax.lax.dot_general(
                byz[key],
                g_ref[:, comp],
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
                precision=CONTRACTION_PRECISION,
            )
            # e[c,p] = sum_m wx * H                         (VPU), placed on
            # its lane of the packed (CB, cap, 6) tile
            e = jnp.sum(w[(0, stagger[0])] * h, axis=-1, keepdims=True)
            out = jnp.where(lane == comp, e, out)
        # (d) one packed per-bin value tile
        o_ref[...] = out.astype(o_ref.dtype)

    return kernel


def fused_gather_bytes_per_cell(cap: int, order: int, *, tiled: bool = False) -> int:
    """VMEM working set of one cell in the fused gather kernel, in bytes.

    ``tiled`` (compiled Mosaic): the (cap, 3) slab, the packed (6, T, T*T)
    neighborhoods and the (cap, 6) output tile, each twice (double-buffered
    pipeline), plus the weight sets — two (cap, T), four (cap, T*T) — the
    four (cap, T*T) byz products, the live (cap, T) H and wx*H, and the
    (cap, 6) accumulator, all (8, 128)-padded. Untiled: the interpreter's
    calibrated count."""
    t, _ = unified_support(order)
    n = t * t
    if not tiled:
        return 4 * (cap * 3 + 6 * t * t * t + 6 * cap * t + 4 * cap * t * t + cap * t + 2 * cap * 6)
    io = vmem_bytes((cap, 3), (6, t, n), (cap, 6), tiled=True)
    work = vmem_bytes(*[(cap, t)] * 4, *[(cap, n)] * 8, (cap, 6), tiled=True)
    return 2 * io + work


def fused_gather_pallas(
    d: jax.Array,
    g: jax.Array,
    *,
    order: int,
    block_cells: int | None = None,
    interpret: bool | None = None,
    vmem_budget_bytes: int | None = None,
) -> jax.Array:
    """Fused Ex/Ey/Ez/Bx/By/Bz gather contraction.

    d: (C, cap, 3) fractional offsets pos - cell (gap slots: any value —
       their outputs are never read back through the slot map).
    g: (C, 6, T, T*T) packed per-cell neighborhoods of the six field
       components on the unified window of ``order``.
    Returns (C, cap, 6) float32 per-bin field values in EB_STAGGERS order.
    """
    c, cap, three = d.shape
    assert three == 3
    t, _ = unified_support(order)
    assert g.shape == (c, 6, t, t * t), f"expected {(c, 6, t, t * t)}, got {g.shape}"

    interpret = resolve_interpret(interpret)
    if block_cells is None:
        block_cells = choose_block_cells(
            c,
            fused_gather_bytes_per_cell(cap, order, tiled=not interpret),
            vmem_budget_bytes=vmem_budget_bytes,
            interpret=interpret,
            taps=t,
        )
    cb = min(block_cells, c)

    grid = (pl.cdiv(c, cb),)
    return pl.pallas_call(
        _make_fused_gather_kernel(order),
        grid=grid,
        in_specs=[
            pl.BlockSpec((cb, cap, 3), lambda i: (i, 0, 0)),
            pl.BlockSpec((cb, 6, t, t * t), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((cb, cap, 6), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, cap, 6), jnp.float32),
        interpret=interpret,
    )(d, g)
