"""Pallas TPU kernels: binned outer-product deposition (the MOPA analogue).

Two kernels live here.

`bin_outer_product_pallas` — the original single-component contraction
  out[c] = A_c^T @ B_c with the operand tensors A/B built *outside* the
  kernel (they round-trip through HBM). Kept as a comparison mode and for
  generic batched-contraction use.

`fused_deposition_pallas` — the fused three-component megakernel
(paper Alg. 2, "VPU preprocessing + MPU accumulation in one pipeline").
Per cell-block it:

  (a) loads the gathered binned particle slab: fractional offsets
      ``d:(C, cap, 3)`` and per-component values ``val:(C, cap, 3)``
      (val[c,p,k] = q*w*v_k, zeroed for gap slots);
  (b) computes the six 1-D shape-weight sets (staggered + unstaggered per
      axis) in-kernel on the VPU, on the order's *unified* tap window
      (shape_functions.unified_support) so all components share shapes;
  (c) runs the three MXU contractions for Jx/Jy/Jz against those shared
      weights (component k uses the staggered set on axis k);
  (d) writes one packed ``(C, 3, T, T*T)`` rhocell tensor.

The A/B operand tensors therefore never exist in HBM — only the (C, cap, 3)
slabs stream in and the packed rhocell tiles stream out, and the bin gather
happens once for all three components instead of three times.

TPU mapping (DESIGN.md §2): the per-cell sum of outer products IS the MPU
tile accumulation — a contraction over the bin-capacity axis executed as a
batched dot on the MXU. The grid tiles the cell axis; block sizes come from
the shared VMEM-budget autotuner (kernels/common.py). Capacity should be a
multiple of 8 (lane alignment; 128 for full MXU depth — choose_capacity()).

Weight evaluation is `shape_functions.lane_axis_weights`: the same
B-spline the pure-JAX reference evaluates through `shape_weights_window`,
with the taps on the lane axis (a broadcasted iota), so the kernel body
needs no stack, reshape or scatter — none of which Mosaic lowers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.shape_functions import CONTRACTION_PRECISION, lane_axis_weights, unified_support
from repro.kernels.common import (
    choose_block_cells,
    resolve_interpret,
    vmem_bytes,
)


def _mxu_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...]
    b = b_ref[...]
    o_ref[...] = jax.lax.dot_general(
        a,
        b,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=o_ref.dtype,
        precision=CONTRACTION_PRECISION,
    )


def _vpu_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...]  # (CB, cap, M)
    b = b_ref[...]  # (CB, cap, N)
    o_ref[...] = jnp.sum(a[:, :, :, None] * b[:, :, None, :], axis=1, dtype=o_ref.dtype)


def bin_outer_product_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_cells: int | None = None,
    mode: str = "mxu",
    interpret: bool | None = None,
    vmem_budget_bytes: int | None = None,
) -> jax.Array:
    """Batched per-bin contraction via pl.pallas_call.

    a: (C, cap, M), b: (C, cap, N) -> (C, M, N) float32.
    """
    c, cap, m = a.shape
    n = b.shape[2]
    assert b.shape[:2] == (c, cap)

    interpret = resolve_interpret(interpret)
    if block_cells is None:
        # double-buffered in/out blocks
        per_cell = 2 * vmem_bytes((cap, m), (cap, n), (m, n), tiled=not interpret)
        block_cells = choose_block_cells(
            c, per_cell, vmem_budget_bytes=vmem_budget_bytes, interpret=interpret
        )
    cb = min(block_cells, c)

    kernel = _mxu_kernel if mode == "mxu" else _vpu_kernel
    grid = (pl.cdiv(c, cb),)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((cb, cap, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((cb, cap, n), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((cb, m, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, m, n), jnp.float32),
        interpret=interpret,
    )(a, b)


# ---------------------------------------------------------------------------
# Fused three-component megakernel
# ---------------------------------------------------------------------------


def _current_tiles(d_ref, val_ref, order: int):
    """Steps (b)+(c) of the fused deposition inside a kernel body: the six
    1-D weight sets on the VPU and the three shared-weight MXU
    contractions, each producing component k's packed ``(CB, T, T*T)``
    rhocell tile on the order's unified window directly.

    Every operand lives on the unified window (off-support taps are exact
    zeros — shape_functions.shape_weights_window), with the taps on lanes
    (`lane_axis_weights`), so the tile needs neither a reshape nor an
    embedding scatter: Mosaic lowers neither. The padded taps cost nothing
    on the MXU, whose tiles are 128 lanes wide anyway."""
    w = lane_axis_weights(d_ref, order)
    tiles = []
    for comp in range(3):
        a = w[(0, comp == 0)] * val_ref[:, :, comp : comp + 1]  # (CB, cap, T)
        byz = w[(1, comp == 1)] * w[(2, comp == 2)]              # (CB, cap, T*T)
        tiles.append(jax.lax.dot_general(
            a,
            byz,
            dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=CONTRACTION_PRECISION,
        ))
    return tiles


def _make_fused_kernel(order: int):
    def kernel(d_ref, val_ref, o_ref):
        # (d) each component's tile is written straight into its slot of
        # the packed (CB, 3, T, T*T) output block
        for comp, tile in enumerate(_current_tiles(d_ref, val_ref, order)):
            o_ref[:, comp] = tile.astype(o_ref.dtype)

    return kernel


def fused_deposition_bytes_per_cell(cap: int, order: int, *, tiled: bool = False) -> int:
    """VMEM working set of one cell in the fused kernel, in bytes.

    ``tiled`` (compiled Mosaic): the two (cap, 3) input slabs and the
    packed (3, T, T*T) output tile, each twice (double-buffered pipeline),
    plus the weight sets — two (cap, T), four (cap, T*T) — the live
    (cap, T) and (cap, T*T) operands and three (T, T*T) result tiles, all
    (8, 128)-padded. Untiled: the interpreter's calibrated count (inputs,
    six (cap, T) weight sets, one operand pair, the packed tile twice)."""
    t, _ = unified_support(order)
    n = t * t
    if not tiled:
        return 4 * (2 * cap * 3 + 6 * cap * t + cap * (t + n) + 2 * 3 * t * n)
    io = vmem_bytes((cap, 3), (cap, 3), (3, t, n), tiled=True)
    work = vmem_bytes(*[(cap, t)] * 3, *[(cap, n)] * 5, *[(t, n)] * 3, tiled=True)
    return 2 * io + work


def fused_deposition_pallas(
    d: jax.Array,
    val: jax.Array,
    *,
    order: int,
    block_cells: int | None = None,
    interpret: bool | None = None,
    vmem_budget_bytes: int | None = None,
) -> jax.Array:
    """Fused Jx/Jy/Jz deposition contraction.

    d:   (C, cap, 3) fractional offsets pos - cell (gap slots: any value).
    val: (C, cap, 3) q*w*v per component (gap slots MUST be zero — they
         carry the masking, exactly like the zero rows of A in the
         unfused kernel).
    Returns (C, 3, T, T*T) float32 packed rhocell tiles on the unified
    window of ``order`` (T, base = unified_support(order)).
    """
    c, cap, three = d.shape
    assert three == 3 and val.shape == d.shape
    t, _ = unified_support(order)

    interpret = resolve_interpret(interpret)
    if block_cells is None:
        block_cells = choose_block_cells(
            c,
            fused_deposition_bytes_per_cell(cap, order, tiled=not interpret),
            vmem_budget_bytes=vmem_budget_bytes,
            interpret=interpret,
            taps=t,
        )
    cb = min(block_cells, c)

    grid = (pl.cdiv(c, cb),)
    return pl.pallas_call(
        _make_fused_kernel(order),
        grid=grid,
        in_specs=[
            pl.BlockSpec((cb, cap, 3), lambda i: (i, 0, 0)),
            pl.BlockSpec((cb, cap, 3), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((cb, 3, t, t * t), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, 3, t, t * t), jnp.float32),
        interpret=interpret,
    )(d, val)


# ---------------------------------------------------------------------------
# Epilogue-fused megakernel: rhocell z-reduction inside the kernel
# ---------------------------------------------------------------------------


def _make_fused_reduced_kernel(order: int, nz: int, guard: int):
    t, base = unified_support(order)
    g = guard

    def kernel(d_ref, val_ref, o_ref):
        bc = d_ref.shape[0] // nz  # BC whole z-columns of cells

        # (b)+(c) as in the packed kernel, then (d) the rhocell z-pass
        # *in-kernel*: because cells are laid out z-fastest, a block of
        # whole columns keeps every shifted add of
        # reduce_rhocell_separable's acc_z stage inside the block — the
        # packed (C, 3, T, T*T) tile never exists in HBM, and the output
        # shrinks from 3*T^3 to 3*T^2*(nz+2g)/nz floats per cell. Tap adds
        # run in ascending unified-window order into the output block, the
        # same per-element accumulation sequence as the two-step reference.
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        for comp, tile in enumerate(_current_tiles(d_ref, val_ref, order)):
            rho = tile.reshape(bc, nz, t, t, t)
            for c in range(t):
                z0 = g + base + c
                o_ref[:, comp, z0 : z0 + nz] += rho[..., c].astype(o_ref.dtype)

    return kernel


def fused_reduced_bytes_per_column(
    cap: int, order: int, nz: int, guard: int, *, tiled: bool = False
) -> int:
    """VMEM working set of one z-column in the epilogue-fused kernel: nz
    cells of the fused working set plus the column's (3, nz+2g, T, T)
    accumulator."""
    t, _ = unified_support(order)
    acc = vmem_bytes((3 * (nz + 2 * guard), t, t), tiled=tiled)
    return nz * fused_deposition_bytes_per_cell(cap, order, tiled=tiled) + acc


def fused_deposition_reduced_pallas(
    d: jax.Array,
    val: jax.Array,
    *,
    order: int,
    grid_shape,
    guard: int,
    block_cols: int | None = None,
    interpret: bool | None = None,
    vmem_budget_bytes: int | None = None,
) -> jax.Array:
    """Fused deposition with the rhocell z-reduction folded in-kernel.

    Same (C, cap, 3) slab inputs as `fused_deposition_pallas`, but the grid
    tiles whole z-columns (cells are z-fastest, so a column is ``nz``
    consecutive cells) and each block accumulates its packed tiles straight
    into a per-column ``(3, nz+2g, T, T)`` z-reduced accumulator. Returns
    ``(nx*ny, 3, nz+2g, T, T)`` float32 — finish with
    ``core.rhocell.reduce_rhocell_tail`` per component.
    """
    nx, ny, nz = grid_shape
    c, cap, three = d.shape
    assert three == 3 and val.shape == d.shape
    assert c == nx * ny * nz, (c, grid_shape)
    n_cols = nx * ny
    t, _ = unified_support(order)
    g = guard

    interpret = resolve_interpret(interpret)
    if block_cols is None:
        block_cols = choose_block_cells(
            n_cols,
            fused_reduced_bytes_per_column(cap, order, nz, g, tiled=not interpret),
            vmem_budget_bytes=vmem_budget_bytes,
            interpret=interpret,
            taps=t,
        )
    bc = min(block_cols, n_cols)

    grid = (pl.cdiv(n_cols, bc),)
    return pl.pallas_call(
        _make_fused_reduced_kernel(order, nz, g),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc * nz, cap, 3), lambda i: (i, 0, 0)),
            pl.BlockSpec((bc * nz, cap, 3), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bc, 3, nz + 2 * g, t, t), lambda i: (i, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_cols, 3, nz + 2 * g, t, t), jnp.float32),
        interpret=interpret,
    )(d, val)
