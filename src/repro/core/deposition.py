"""Current/charge deposition — the paper's hot kernel, three ways.

Implementations (paper §5.2.1 evaluation set):

  deposit_scatter   — WarpX-style baseline: per-particle scatter-add of the
                      (order+1)^3 nodal contributions straight into the grid
                      (the "atomicAdd" pattern; on TPU a serializing
                      gather/scatter-engine op). Also the float64-checkable
                      oracle.
  deposit_rhocell   — Vincenti et al. VPU analogue: per-particle tap weights
                      scatter into the *per-cell* rhocell rows (conflicts only
                      within a cell), then one dense reduction.
  deposit_matrix    — Matrix-PIC: particles binned by cell (gaps = zero
                      weight); per-cell contributions become ONE contraction
                      rhocell[c] = A_c^T B_c over the bin axis — a batched
                      matmul that maps onto the MXU (sum of outer products ==
                      the paper's accumulated MOPA tile). No scatter anywhere
                      in the hot path.

All three return a guard-padded grid (periodic folding is the caller's
choice) so they are directly comparable and usable under domain
decomposition (guard exchange instead of fold).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import shape_functions as sf
from repro.core.binning import BinnedLayout, BinSlab, bin_slab_values, build_bin_slab, cell_coords
from repro.core.rhocell import (
    fold_guards,
    reduce_rhocell,
    reduce_rhocell_separable,
    reduce_rhocell_tail,
)

Stagger = tuple[bool, bool, bool]

NO_STAGGER: Stagger = (False, False, False)
STAGGER_X: Stagger = (True, False, False)
STAGGER_Y: Stagger = (False, True, False)
STAGGER_Z: Stagger = (False, False, True)


def _taps_and_bases(order: int, stagger: Stagger):
    t, b = zip(*(sf.support(order, s) for s in stagger))
    return t, b


def _per_dim_weights(pos, cells, order: int, stagger: Stagger):
    """1-D shape factors per dimension. pos/cells: (..., 3)."""
    d = pos - cells.astype(pos.dtype)
    return [sf.shape_weights(d[..., k], order, stagger[k]) for k in range(3)]


# ---------------------------------------------------------------------------
# Baseline: direct scatter-add (WarpX analogue + oracle)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("grid_shape", "order", "stagger", "guard"))
def deposit_scatter(pos, values, *, grid_shape, order: int, stagger: Stagger = NO_STAGGER, guard: int | None = None):
    """Scatter-add deposition. pos: (Np,3) grid units; values: (Np,) q*w*v.

    Returns guard-padded grid (nx+2g, ny+2g, nz+2g).
    """
    nx, ny, nz = grid_shape
    g = sf.max_guard(order) if guard is None else guard
    cells = jnp.floor(pos).astype(jnp.int32)
    wx, wy, wz = _per_dim_weights(pos, cells, order, stagger)
    (tx, ty, tz), (bx, by, bz) = _taps_and_bases(order, stagger)

    w3 = wx[:, :, None, None] * wy[:, None, :, None] * wz[:, None, None, :]
    contrib = values[:, None, None, None] * w3  # (Np, tx, ty, tz)

    nxp, nyp, nzp = nx + 2 * g, ny + 2 * g, nz + 2 * g
    ix = cells[:, 0, None] + (bx + g) + jnp.arange(tx)
    iy = cells[:, 1, None] + (by + g) + jnp.arange(ty)
    iz = cells[:, 2, None] + (bz + g) + jnp.arange(tz)
    flat = (
        (ix[:, :, None, None] * nyp + iy[:, None, :, None]) * nzp
        + iz[:, None, None, :]
    )
    grid = jnp.zeros((nxp * nyp * nzp,), values.dtype)
    grid = grid.at[flat.reshape(-1)].add(contrib.reshape(-1))
    return grid.reshape(nxp, nyp, nzp)


# ---------------------------------------------------------------------------
# Vincenti-style rhocell (VPU analogue)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("grid_shape", "order", "stagger", "guard"))
def deposit_rhocell(pos, values, cell_ids, *, grid_shape, order: int, stagger: Stagger = NO_STAGGER, guard: int | None = None):
    """Per-particle taps scatter into the per-cell rhocell row, then one
    dense reduction (Eq. 5). Conflicts are confined to a cell's row."""
    nx, ny, nz = grid_shape
    g = sf.max_guard(order) if guard is None else guard
    n_cells = nx * ny * nz
    cells = jnp.floor(pos).astype(jnp.int32)
    wx, wy, wz = _per_dim_weights(pos, cells, order, stagger)
    (tx, ty, tz), bases = _taps_and_bases(order, stagger)

    w3 = wx[:, :, None, None] * wy[:, None, :, None] * wz[:, None, None, :]
    contrib = (values[:, None, None, None] * w3).reshape(-1, tx * ty * tz)

    rho = jnp.zeros((n_cells, tx * ty * tz), values.dtype)
    rho = rho.at[cell_ids].add(contrib)
    return reduce_rhocell(rho.reshape(n_cells, tx, ty, tz), grid_shape, bases, g)


# ---------------------------------------------------------------------------
# Matrix-PIC: binned outer-product deposition
# ---------------------------------------------------------------------------

def binned_shape_factors(pos, values, layout: BinnedLayout, *, grid_shape, order: int, stagger: Stagger):
    """Stage-1 "VPU preprocessing" (Alg. 2): gather the bin's particle data
    and build the MPU operand tensors.

    Returns:
      A:   (n_cells, cap, Tx)     w_p * s_x factors (gaps -> exact 0 rows)
      B:   (n_cells, cap, Ty*Tz)  s_y (x) s_z factors
    """
    slots = layout.slots
    n_cells, cap = slots.shape
    p = jnp.maximum(slots, 0)
    valid = slots >= 0

    pos_b = pos[p]                                  # (C, cap, 3)
    val_b = jnp.where(valid, values[p], jnp.zeros((), values.dtype))
    cells = cell_coords(n_cells, grid_shape)        # (C, 3)
    d = pos_b - cells[:, None, :].astype(pos.dtype)

    wx = sf.shape_weights(d[..., 0], order, stagger[0])
    wy = sf.shape_weights(d[..., 1], order, stagger[1])
    wz = sf.shape_weights(d[..., 2], order, stagger[2])

    a = wx * val_b[..., None]                       # (C, cap, Tx)
    b = (wy[..., :, None] * wz[..., None, :]).reshape(n_cells, cap, -1)
    return a, b


def _default_bin_matmul(a, b):
    """rhocell[c] = A_c^T B_c — the sum-of-outer-products == MOPA tile."""
    return jnp.einsum("cpm,cpn->cmn", a, b, precision=sf.CONTRACTION_PRECISION)


@partial(
    jax.jit,
    static_argnames=(
        "grid_shape", "order", "stagger", "guard", "bin_matmul", "separable_reduce", "backend",
    ),
)
def _deposit_matrix_jit(
    pos,
    values,
    layout: BinnedLayout,
    *,
    grid_shape,
    order: int,
    stagger: Stagger,
    guard: int | None,
    bin_matmul: Callable | None,
    separable_reduce: bool,
    backend: str | None,
):
    g = sf.max_guard(order) if guard is None else guard
    (tx, ty, tz), bases = _taps_and_bases(order, stagger)

    a, b = binned_shape_factors(pos, values, layout, grid_shape=grid_shape, order=order, stagger=stagger)
    mm = bin_matmul
    if mm is None and backend is not None:
        from repro.kernels import dispatch

        name = dispatch.resolve(
            "deposit_unfused", backend, order=order, grid_shape=grid_shape,
            capacity=a.shape[1], dtype=str(values.dtype),
        )
        if name == "pallas":
            from repro.kernels.deposition.ops import bin_outer_product

            mm = bin_outer_product
    mm = mm or _default_bin_matmul
    rho = mm(a, b).reshape(-1, tx, ty, tz)

    reduce = reduce_rhocell_separable if separable_reduce else reduce_rhocell
    return reduce(rho, grid_shape, bases, g)


def deposit_matrix(
    pos,
    values,
    layout: BinnedLayout,
    *,
    grid_shape,
    order: int,
    stagger: Stagger = NO_STAGGER,
    guard: int | None = None,
    bin_matmul: Callable | None = None,
    separable_reduce: bool = True,
    backend: str | None = None,
    batch: int = 1,
):
    """Matrix-PIC deposition for one current component.

    `bin_matmul` lets the Pallas kernel (kernels/deposition) replace the
    einsum; default is the jnp contraction (identical math). ``backend``
    selects the contraction through the kernel dispatcher instead
    ("auto"/"xla"/"pallas" — see kernels.dispatch); an explicit
    ``bin_matmul`` wins over ``backend``.

    Eager wrapper: the backend resolves BEFORE the jitted impl traces, so
    an eager "auto" call can genuinely benchmark (the dispatcher never
    measures under an ambient trace — callers that trace this should
    prewarm the key first, as the sim drivers do).
    """
    if bin_matmul is None and backend is not None:
        from repro.kernels import dispatch

        backend = dispatch.resolve(
            "deposit_unfused", backend, order=order, grid_shape=tuple(grid_shape),
            capacity=layout.slots.shape[1], dtype=str(values.dtype), batch=batch,
        )
    return _deposit_matrix_jit(
        pos, values, layout, grid_shape=tuple(grid_shape), order=order, stagger=stagger,
        guard=guard, bin_matmul=bin_matmul, separable_reduce=separable_reduce,
        backend=backend,
    )


# ---------------------------------------------------------------------------
# Convenience: full current density (Jx, Jy, Jz) with Yee staggering
# ---------------------------------------------------------------------------

CURRENT_STAGGER: tuple[Stagger, Stagger, Stagger] = (STAGGER_X, STAGGER_Y, STAGGER_Z)


def fused_bin_slab(pos, vel, qw, layout: BinnedLayout, *, grid_shape):
    """One bin gather for all three current components (Alg. 2 stage 1).

    Returns the two (n_cells, cap, 3) slabs the fused megakernel streams:
      d:   fractional offsets pos - cell (gap slots: whatever particle 0
           aliases to — harmless, the value slab carries the masking)
      val: q*w*v per component, exactly 0 on gap/overflow slots.

    Compare binned_shape_factors: that builds the full A:(C,cap,Tx) /
    B:(C,cap,Ty*Tz) operand tensors per component in HBM; here only these
    two thin slabs exist outside the kernel. The position staging is the
    shared `binning.build_bin_slab` (a `BinSlab`), so a caller that already
    holds the step's slab passes it to `deposit_current_matrix_fused`
    directly and this function never runs.
    """
    slab = build_bin_slab(pos, layout, grid_shape=grid_shape)
    return slab.d, bin_slab_values(vel, qw, layout, slab)


def _fused_grids_xla(d, val, *, grid_shape, order, guard, reduce):
    """The pure-XLA fused route: six shared weight sets, each component
    contracted on its TRUE support (no padded FLOPs)."""
    n_cells, cap, _ = d.shape
    w_u = [sf.shape_weights(d[..., k], order, False) for k in range(3)]  # unstaggered
    w_s = [sf.shape_weights(d[..., k], order, True) for k in range(3)]   # staggered
    out = []
    for comp in range(3):
        stagger = CURRENT_STAGGER[comp]
        (tx, ty, tz), bases = _taps_and_bases(order, stagger)
        wx = w_s[0] if stagger[0] else w_u[0]
        wy = w_s[1] if stagger[1] else w_u[1]
        wz = w_s[2] if stagger[2] else w_u[2]
        a = wx * val[..., comp][..., None]
        byz = (wy[..., :, None] * wz[..., None, :]).reshape(n_cells, cap, -1)
        rho = _default_bin_matmul(a, byz).reshape(-1, tx, ty, tz)
        out.append(reduce(rho, grid_shape, bases, guard))
    return out


def _fused_grids_packed(packed, val_dtype, *, grid_shape, order, guard, reduce):
    """Finish the Pallas megakernel's packed (C, 3, T, T*T) tiles: one
    rhocell reduction per component on the unified window."""
    t, base = sf.unified_support(order)
    bases = (base, base, base)
    return [
        reduce(packed[:, comp].astype(val_dtype).reshape(-1, t, t, t), grid_shape, bases, guard)
        for comp in range(3)
    ]


def _fused_grids_reduced(acc, val_dtype, *, grid_shape, order, guard):
    """Finish the epilogue-fused megakernel's (C_xy, 3, nz+2g, T, T)
    accumulators: the z pass already happened in-kernel, only the shared
    y/x tail (reduce_rhocell_tail) remains — the exact op sequence
    reduce_rhocell_separable would have run, which is the bit-parity
    contract with the two-step route."""
    nx, ny, nz = grid_shape
    g = guard
    t, base = sf.unified_support(order)
    return [
        reduce_rhocell_tail(
            acc[:, comp].astype(val_dtype).reshape(nx, ny, nz + 2 * g, t, t),
            grid_shape, (base, base), g,
        )
        for comp in range(3)
    ]


def _fused_deposit_grids_impl(d, val, *, grid_shape, order, guard, backend, separable_reduce):
    """Slab -> [Jx, Jy, Jz] guard-padded via a dispatcher backend name.

    ``backend`` is normally already a concrete name (the public wrappers
    resolve eagerly before tracing); the resolve here maps it through
    availability fallback — and still handles an "auto" that reaches a
    traced body directly (memo/cache hit, else priority order: the
    dispatcher never benchmarks under an ambient trace).
    """
    from repro.kernels import dispatch

    reduce = reduce_rhocell_separable if separable_reduce else reduce_rhocell
    name = dispatch.resolve(
        "deposit_fused", backend, order=order, grid_shape=grid_shape,
        capacity=d.shape[1], dtype=str(val.dtype),
    )
    if name == "pallas_reduced":
        from repro.kernels.deposition.ops import fused_bin_deposit_reduced

        acc = fused_bin_deposit_reduced(d, val, order=order, grid_shape=grid_shape, guard=guard)
        return _fused_grids_reduced(acc, val.dtype, grid_shape=grid_shape, order=order, guard=guard)
    if name == "pallas":
        from repro.kernels.deposition.ops import fused_bin_deposit

        packed = fused_bin_deposit(d, val, order=order)
        return _fused_grids_packed(
            packed, val.dtype, grid_shape=grid_shape, order=order, guard=guard, reduce=reduce
        )
    return _fused_grids_xla(d, val, grid_shape=grid_shape, order=order, guard=guard, reduce=reduce)


@partial(jax.jit, static_argnames=("grid_shape", "order", "guard", "backend", "separable_reduce"))
def _fused_deposit_grids_jit(d, val, *, grid_shape, order, guard, backend, separable_reduce):
    return _fused_deposit_grids_impl(
        d, val, grid_shape=grid_shape, order=order, guard=guard,
        backend=backend, separable_reduce=separable_reduce,
    )


def fused_deposit_grids(
    d,
    val,
    *,
    grid_shape,
    order: int,
    guard: int | None = None,
    backend: str = "xla",
    separable_reduce: bool = True,
    batch: int = 1,
):
    """Post-slab fused deposition: (C, cap, 3) offsets + values ->
    [Jx, Jy, Jz] guard-padded, via the named dispatcher backend. This is
    the exact portion of the hot path the backends disagree on, so it is
    also what the dispatcher's "auto" benchmark times (kernels.dispatch
    builds its deposit_fused thunks on this entry point).

    Eager wrapper: ``backend`` resolves to a concrete name BEFORE the
    jitted impl traces, so an eager "auto" call benchmarks real device
    execution (the dispatcher never measures under an ambient trace)."""
    from repro.kernels import dispatch

    g = sf.max_guard(order) if guard is None else guard
    name = dispatch.resolve(
        "deposit_fused", backend, order=order, grid_shape=tuple(grid_shape),
        capacity=d.shape[1], dtype=str(val.dtype), batch=batch,
    )
    return _fused_deposit_grids_jit(
        d, val, grid_shape=tuple(grid_shape), order=order, guard=g,
        backend=name, separable_reduce=separable_reduce,
    )


@partial(
    jax.jit,
    static_argnames=("grid_shape", "order", "guard", "fused_matmul", "separable_reduce", "backend"),
)
def _deposit_current_matrix_fused_jit(
    pos,
    vel,
    qw,
    layout: BinnedLayout,
    *,
    grid_shape,
    order: int,
    guard: int | None,
    fused_matmul: Callable | None,
    separable_reduce: bool,
    slab: BinSlab | None,
    backend: str | None,
    values=None,
):
    g = sf.max_guard(order) if guard is None else guard
    if slab is None:
        slab = build_bin_slab(pos, layout, grid_shape=grid_shape)
    d = slab.d
    val = values if values is not None else bin_slab_values(vel, qw, layout, slab)
    reduce = reduce_rhocell_separable if separable_reduce else reduce_rhocell

    if fused_matmul is not None:
        packed = fused_matmul(d, val, order=order)
        return _fused_grids_packed(
            packed, val.dtype, grid_shape=grid_shape, order=order, guard=g, reduce=reduce
        )
    if backend is not None:
        return _fused_deposit_grids_impl(
            d, val, grid_shape=grid_shape, order=order, guard=g,
            backend=backend, separable_reduce=separable_reduce,
        )
    return _fused_grids_xla(d, val, grid_shape=grid_shape, order=order, guard=g, reduce=reduce)


def deposit_current_matrix_fused(
    pos,
    vel,
    qw,
    layout: BinnedLayout,
    *,
    grid_shape,
    order: int,
    guard: int | None = None,
    fused_matmul: Callable | None = None,
    separable_reduce: bool = True,
    slab: BinSlab | None = None,
    backend: str | None = None,
    batch: int = 1,
    values=None,
):
    """All three Yee-staggered current components in one fused pass — the
    default `Simulation` deposition hot path (paper Alg. 2).

    The bin gather happens ONCE (fused_bin_slab) and the six 1-D weight
    sets (staggered + unstaggered per axis) are evaluated once and shared
    across Jx/Jy/Jz on the order's unified tap window — the per-component
    path re-gathers and re-computes 2.5x of this work, and materializes
    full A/B operand tensors in HBM per component.

    `fused_matmul` is the slab -> packed (C, 3, T, T*T) contraction:
    kernels.deposition.fused_bin_deposit (the Pallas megakernel, in-kernel
    operand build on the VPU + three shared-weight MXU contractions on the
    unified tap window — the zero-padding to T is free on MXU tiles) or
    None for the pure-XLA reference, which contracts each component on its
    TRUE support (no padded FLOPs — XLA einsums pay for every zero) while
    still sharing the slab gather and per-axis weights. Identical math
    either way. Returns [Jx, Jy, Jz] guard-padded.

    ``slab`` is the step's prebuilt `BinSlab` (must be consistent with
    ``pos``/``layout``): when given, the slot-table position staging is
    NOT repeated here — only the velocity-dependent q·w·v values are
    gathered against the same slot table (`bin_slab_values`), so the one
    slab the step built serves the field gather AND this deposition.
    ``values`` goes one further: a caller that staged the q·w·v slab
    together with the positions (`binning.bin_slab_staging`, the fused
    push-into-bin-order path both sim drivers use) passes it here and NO
    slot-table gather runs inside the deposition at all.

    ``backend`` routes the post-slab contraction through the kernel
    dispatcher ("auto"/"xla"/"pallas"/"pallas_reduced" — kernels.dispatch;
    "pallas_reduced" folds the rhocell z-reduction into the kernel
    epilogue and is inherently separable). An explicit ``fused_matmul``
    callable wins over ``backend`` (legacy/ablation hook).

    Eager wrapper: ``backend`` resolves BEFORE the jitted impl traces, so
    an eager "auto" call genuinely benchmarks (the dispatcher never
    measures under an ambient trace — the sim drivers, which trace this
    inside their step, prewarm the key at setup instead).
    """
    if fused_matmul is None and backend is not None:
        from repro.kernels import dispatch

        backend = dispatch.resolve(
            "deposit_fused", backend, order=order, grid_shape=tuple(grid_shape),
            capacity=layout.slots.shape[1],
            dtype=str(jnp.result_type(vel.dtype, qw.dtype)), batch=batch,
        )
    return _deposit_current_matrix_fused_jit(
        pos, vel, qw, layout, grid_shape=tuple(grid_shape), order=order, guard=guard,
        fused_matmul=fused_matmul, separable_reduce=separable_reduce, slab=slab,
        backend=backend, values=values,
    )


def deposit_current(pos, vel, qw, *, grid_shape, order: int, method: str = "matrix", layout: BinnedLayout | None = None, cell_ids=None, fold: bool = True, **kw):
    """Deposit all three Yee-staggered current components.

    vel: (Np, 3); qw: (Np,) charge*weight. method in {scatter, rhocell,
    matrix, matrix_unfused}; "matrix" is the fused megakernel path,
    "matrix_unfused" the per-component comparison mode.
    Returns list [Jx, Jy, Jz], folded periodic grids if fold else padded.
    """
    # fold with the guard the deposit actually used, not max_guard
    # unconditionally — a caller-supplied guard= kwarg would otherwise fold
    # interior current onto the wrong cells without an error
    g = kw.get("guard")
    g = sf.max_guard(order) if g is None else g
    if method == "matrix":
        assert layout is not None
        out = deposit_current_matrix_fused(pos, vel, qw, layout, grid_shape=grid_shape, order=order, **kw)
        return [fold_guards(j, g) if fold else j for j in out]
    out = []
    for comp in range(3):
        values = qw * vel[:, comp]
        stagger = CURRENT_STAGGER[comp]
        if method == "scatter":
            j = deposit_scatter(pos, values, grid_shape=grid_shape, order=order, stagger=stagger, **kw)
        elif method == "rhocell":
            assert cell_ids is not None
            j = deposit_rhocell(pos, values, cell_ids, grid_shape=grid_shape, order=order, stagger=stagger, **kw)
        elif method == "matrix_unfused":
            assert layout is not None
            j = deposit_matrix(pos, values, layout, grid_shape=grid_shape, order=order, stagger=stagger, **kw)
        else:
            raise ValueError(f"unknown method {method}")
        out.append(fold_guards(j, g) if fold else j)
    return out
