"""B-spline particle shape functions (CIC / TSC / QSP) with fixed-support taps.

Conventions
-----------
Positions are in *grid units*: a particle at ``x`` lives in cell
``c = floor(x)`` with fractional offset ``d = x - c in [0, 1)``.

Unstaggered nodes sit at integer coordinates ``i``; staggered nodes (Yee
half-grid, used for current components along their own axis) sit at
``i + 1/2``.

The paper's deposition orders map to B-spline orders (WarpX
``algo.particle_shape``):

  order 1  CIC   (linear,   support 2)
  order 2  TSC   (quadratic, support 3)
  order 3  QSP   (cubic,     support 4)   -- the paper's "third-order QSP"

TPU adaptation (DESIGN.md §2): to keep the per-cell rhocell reduction a
*fixed-offset dense shifted add* we use a fixed tap window per
``(order, staggered)`` wide enough to cover the support for every
``d in [0,1)``; taps outside the true support evaluate to exactly 0 through
the piecewise B-spline. The window is ``SUPPORT[(order, staggered)]``:
``(n_taps, base_offset)`` with node offsets ``base .. base+n_taps-1``
relative to the particle's cell index.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Precision of every bin contraction (deposition, gather, and their
#: kernels). TPU's default f32 matmul is one bf16 pass, whose ~1e-3
#: relative error misses the 1e-5 bound the float64 scatter oracle holds
#: deposition and gather to; HIGHEST runs the f32 contraction in full.
#: A no-op on CPU.
CONTRACTION_PRECISION = jax.lax.Precision.HIGHEST

# (order, staggered) -> (n_taps, base_offset)
SUPPORT: dict[tuple[int, bool], tuple[int, int]] = {
    (1, False): (2, 0),
    (2, False): (4, -1),   # widened: true support 3, base depends on d
    (3, False): (4, -1),
    (1, True): (3, -1),    # widened: true support 2
    (2, True): (3, -1),
    (3, True): (5, -2),    # widened: true support 4
}

ORDERS = (1, 2, 3)

# FLOPs of the canonical *scalar* deposition algorithm per particle (one
# current component = (o+1)^3 fma*2 + 1D factor math), used for the paper's
# "effective computational work" metric (419 FLOPs/particle for QSP, 3 comps).
CANONICAL_FLOPS_PER_PARTICLE = {1: 61, 2: 190, 3: 419}


def bspline(order: int, u):
    """Centered B-spline of given order evaluated at (signed) distance u."""
    a = jnp.abs(u)
    if order == 1:
        return jnp.maximum(jnp.asarray(0.0, a.dtype), 1.0 - a)
    if order == 2:
        inner = 0.75 - a * a
        outer = 0.5 * (1.5 - a) ** 2
        zero = jnp.zeros_like(a)
        return jnp.where(a < 0.5, inner, jnp.where(a < 1.5, outer, zero))
    if order == 3:
        inner = 2.0 / 3.0 - a * a + 0.5 * a * a * a
        outer = (2.0 - a) ** 3 / 6.0
        zero = jnp.zeros_like(a)
        return jnp.where(a < 1.0, inner, jnp.where(a < 2.0, outer, zero))
    raise ValueError(f"unsupported shape order {order}")


def shape_weights_window(d, order: int, staggered: bool, *, n_taps: int, base: int):
    """1-D shape factors over an *explicit* tap window.

    This is the single shape-weight evaluation shared by the pure-JAX
    deposition reference AND the Pallas megakernel body (kernels/deposition):
    it is pure elementwise jnp on ``d`` with the tap offsets baked in as a
    numpy constant — no iota, so it traces cleanly inside a TPU kernel
    (Mosaic rejects 1-D iota).

    Taps outside the true B-spline support evaluate to exactly 0, so a
    window wider than SUPPORT[(order, staggered)] (e.g. unified_support's,
    shared across stagger variants) yields the same weights, zero-padded.

    Each tap offset enters as a Python scalar (pallas_call rejects captured
    array constants, and Mosaic rejects 1-D iota), then the taps stack.
    """
    shift = 0.5 if staggered else 0.0
    taps = [bspline(order, d - float(base + shift + j)) for j in range(n_taps)]
    return jnp.stack(taps, axis=-1)


def shape_weights(d, order: int, staggered: bool):
    """1-D shape factors for fractional in-cell position ``d``.

    Args:
      d: (...,) array, fractional position in [0, 1) relative to the cell.
      order: 1 | 2 | 3.
      staggered: whether target nodes sit on the half-grid (i + 1/2).

    Returns:
      (..., T) weights at node offsets ``base .. base+T-1`` (see SUPPORT).
      Rows sum to 1 (partition of unity) for any d in [0, 1).
    """
    n_taps, base = SUPPORT[(order, staggered)]
    return shape_weights_window(d, order, staggered, n_taps=n_taps, base=base)


def support(order: int, staggered: bool) -> tuple[int, int]:
    """(n_taps, base_offset) for the fixed tap window."""
    return SUPPORT[(order, staggered)]


def unified_support(order: int) -> tuple[int, int]:
    """(n_taps, base_offset) of the smallest window covering BOTH the
    staggered and unstaggered supports of ``order``.

    The fused three-component deposition evaluates every current component
    on this one window (extra taps are exactly 0), so Jx/Jy/Jz share operand
    shapes and pack into a single ``(n_cells, 3, T, T*T)`` rhocell tensor:
    order 1 -> (3, -1), order 2 -> (4, -1), order 3 -> (5, -2).
    """
    base = min(SUPPORT[(order, s)][1] for s in (False, True))
    hi = max(SUPPORT[(order, s)][0] + SUPPORT[(order, s)][1] for s in (False, True))
    return hi - base, base


def packed_axis_weights(d, order: int):
    """The six 1-D shape-weight sets of a fused six-component kernel —
    ``(axis, staggered) -> (..., T)`` — all on the order's *unified* tap
    window, computed once and shared by every field/current component.

    Each axis has exactly two variants (centered and staggered: a component
    is staggered on an axis or it is not), so six sets cover all six
    E/B staggers and all three current staggers. On the unified window the
    off-support taps are exactly 0, so every component can contract against
    one packed ``(…, T)`` / ``(…, T·T)`` operand shape — the same sharing
    trick as the fused deposition, here with E and B staggers packed
    together. Pure elementwise jnp on ``d`` (shape_weights_window), so it
    traces inside a Pallas kernel body.

    Args:
      d: (..., 3) fractional in-cell offsets.
    Returns:
      dict {(axis, staggered): (..., T) weights}, T = unified_support(order).
    """
    t, base = unified_support(order)
    return {
        (axis, staggered): shape_weights_window(
            d[..., axis], order, staggered, n_taps=t, base=base
        )
        for axis in (0, 1, 2)
        for staggered in (False, True)
    }


def lane_axis_weights(d, order: int):
    """The six weight sets of `packed_axis_weights` in the layout a TPU
    kernel body can build: ``d`` is a ``(CB, cap, 3)`` slab (array or
    Pallas ref); the x sets are ``(CB, cap, T)`` and the y and z sets live
    on the flattened ``(CB, cap, T*T)`` outer-product axis (lane ``j*T + k``
    holds tap j of y and tap k of z), so ``wy * wz`` is an elementwise
    multiply. Each lane's tap index comes from a broadcasted iota — Mosaic
    lowers no stack or reshape of a lane axis, and this needs neither.
    Same values as `packed_axis_weights`, element for element: every tap
    offset ``base + shift + j`` is exact in float32."""
    t, base = unified_support(order)
    cb, cap, _ = d.shape

    def lane_index(n):
        return jax.lax.broadcasted_iota(jnp.int32, (cb, cap, n), 2).astype(jnp.float32)

    flat = lane_index(t * t)
    tap_y = jnp.floor((flat + 0.5) * (1.0 / t))
    taps = (lane_index(t), tap_y, flat - tap_y * t)
    return {
        (axis, staggered): bspline(
            order, d[:, :, axis : axis + 1] - (taps[axis] + (base + 0.5 * staggered))
        )
        for axis in (0, 1, 2)
        for staggered in (False, True)
    }


def max_guard(order: int) -> int:
    """Guard-cell width needed so every tap of every stagger stays in-range.

    Tap node index range relative to cell c: [c+base, c+base+T-1]. With cells
    in [0, n), node indices span [base, n-1+base+T-1]; a guard of
    g = max(-base, base+T-1-1) + 1 is safe; we return a simple conservative
    bound.
    """
    lo = min(SUPPORT[(order, s)][1] for s in (False, True))
    hi = max(SUPPORT[(order, s)][0] + SUPPORT[(order, s)][1] for s in (False, True))
    return max(-lo, hi - 1)
