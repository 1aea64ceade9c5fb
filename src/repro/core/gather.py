"""Field gather (grid -> particles), the inverse of deposition.

The paper lists gather optimization as future work; we implement it with the
same co-design (beyond-paper, DESIGN.md §7): per-cell the (Tx,Ty,Tz) node
neighbourhood is extracted ONCE with dense shifted slices (shared by all
particles in the bin — the locality the sorter establishes), then each
particle's value is a small contraction against its tap weights:

    E_p = sum_{m,n} wx_p[m] * (B_p[n] * G_c[m, n])     (B = wy (x) wz)

which is again a batched matmul over the bin axis.

Two bin-based routes live here:

* `gather_matrix`   — ONE staggered component per call. Six calls per step
                      (Ex/Ey/Ez/Bx/By/Bz), each re-staging positions into
                      bin order and recomputing per-dim shape weights. Kept
                      as the ``gather="matrix_unfused"`` ablation mode.
* `gather_fields_fused` — all six components in one pass against a
                      prebuilt `BinSlab`: the slot-table position staging
                      happens ONCE per step (shared with the fused
                      deposition), the six 1-D weight sets (centered +
                      staggered per axis) are computed once and shared
                      across components, and the results scatter back to
                      particle order through one slot-map gather. The
                      default ``gather="matrix"`` hot path, with a Pallas
                      megakernel route (kernels/gather) that builds the
                      weights in-kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import shape_functions as sf
from repro.core.binning import BinnedLayout, BinSlab, cell_coords
from repro.core.deposition import NO_STAGGER, Stagger, _per_dim_weights, _taps_and_bases

# Component order of the fused six-component gather: Ex Ey Ez Bx By Bz on
# the standard Yee staggers (must equal pic.grid.E_STAGGER + B_STAGGER —
# pinned by a test; core cannot import pic). Every component is either
# centered or staggered per axis, so the six share the six per-axis weight
# sets of shape_functions.packed_axis_weights.
EB_STAGGERS: tuple[Stagger, ...] = (
    (True, False, False), (False, True, False), (False, False, True),
    (False, True, True), (True, False, True), (True, True, False),
)


@partial(jax.jit, static_argnames=("order", "stagger", "guard"))
def gather_scatter(pos, grid_padded, *, order: int, stagger: Stagger = NO_STAGGER, guard: int | None = None):
    """Baseline per-particle gather from a guard-padded grid. (Np,) values."""
    g = sf.max_guard(order) if guard is None else guard
    cells = jnp.floor(pos).astype(jnp.int32)
    wx, wy, wz = _per_dim_weights(pos, cells, order, stagger)
    (tx, ty, tz), (bx, by, bz) = _taps_and_bases(order, stagger)

    nxp, nyp, nzp = grid_padded.shape
    ix = cells[:, 0, None] + (bx + g) + jnp.arange(tx)
    iy = cells[:, 1, None] + (by + g) + jnp.arange(ty)
    iz = cells[:, 2, None] + (bz + g) + jnp.arange(tz)
    flat = ((ix[:, :, None, None] * nyp + iy[:, None, :, None]) * nzp + iz[:, None, None, :])
    vals = grid_padded.reshape(-1)[flat]  # (Np, tx, ty, tz)
    w3 = wx[:, :, None, None] * wy[:, None, :, None] * wz[:, None, None, :]
    return jnp.sum(vals * w3, axis=(1, 2, 3))


def extract_neighborhoods(grid_padded, grid_shape, *, taps, bases, guard: int):
    """Dense per-cell tap neighbourhoods: (n_cells, Tx, Ty, Tz).

    Pure shifted slicing — the dual of reduce_rhocell."""
    nx, ny, nz = grid_shape
    g = guard
    tx, ty, tz = taps
    bx, by, bz = bases
    blocks = []
    for a in range(tx):
        for b in range(ty):
            for c in range(tz):
                blocks.append(
                    grid_padded[
                        g + bx + a : g + bx + a + nx,
                        g + by + b : g + by + b + ny,
                        g + bz + c : g + bz + c + nz,
                    ]
                )
    stacked = jnp.stack(blocks, axis=-1)  # (nx, ny, nz, tx*ty*tz)
    return stacked.reshape(nx * ny * nz, tx, ty, tz)


@partial(jax.jit, static_argnames=("grid_shape", "order", "stagger", "guard", "bin_gather_op", "backend"))
def _gather_matrix_jit(pos, grid_padded, layout: BinnedLayout, *, grid_shape, order: int, stagger: Stagger, guard: int | None, bin_gather_op, backend: str | None):
    g = sf.max_guard(order) if guard is None else guard
    taps, bases = _taps_and_bases(order, stagger)
    tx, ty, tz = taps
    n_cells, cap = layout.slots.shape

    if bin_gather_op is None and backend is not None:
        from repro.kernels import dispatch

        name = dispatch.resolve(
            "bin_gather", backend, order=order, grid_shape=grid_shape,
            capacity=cap, dtype=str(pos.dtype),
        )
        if name == "pallas":
            from repro.kernels.gather.ops import bin_gather

            bin_gather_op = bin_gather

    neigh = extract_neighborhoods(grid_padded, grid_shape, taps=taps, bases=bases, guard=g)
    neigh = neigh.reshape(n_cells, tx, ty * tz)

    slots = layout.slots
    p = jnp.maximum(slots, 0)
    valid = slots >= 0
    pos_b = pos[p]
    cells = cell_coords(n_cells, grid_shape)
    d = pos_b - cells[:, None, :].astype(pos.dtype)
    wx = sf.shape_weights(d[..., 0], order, stagger[0])
    wy = sf.shape_weights(d[..., 1], order, stagger[1])
    wz = sf.shape_weights(d[..., 2], order, stagger[2])
    byz = (wy[..., :, None] * wz[..., None, :]).reshape(n_cells, cap, ty * tz)

    if bin_gather_op is not None:
        e_bins = bin_gather_op(wx, byz, neigh).astype(pos_b.dtype) * valid
    else:
        # H[c,p,m] = sum_n B[c,p,n] G[c,m,n]; E[c,p] = sum_m wx[c,p,m] H[c,p,m]
        h = jnp.einsum("cpn,cmn->cpm", byz, neigh, precision=sf.CONTRACTION_PRECISION)
        e_bins = jnp.sum(wx * h, axis=-1) * valid

    # scatter back to particle order via the slot map
    e_flat = e_bins.reshape(-1)
    pslot = layout.particle_slot
    return jnp.where(pslot >= 0, e_flat[jnp.maximum(pslot, 0)], jnp.zeros((), e_flat.dtype))


def gather_matrix(pos, grid_padded, layout: BinnedLayout, *, grid_shape, order: int, stagger: Stagger = NO_STAGGER, guard: int | None = None, bin_gather_op=None, backend: str | None = None, batch: int = 1):
    """Binned matrix gather, one component. Returns (Np,) values (0 for
    unslotted particles).

    `bin_gather_op` lets the Pallas kernel (kernels/gather.bin_gather)
    replace the einsum + tap reduction — the ``gather="matrix_unfused"`` +
    Pallas route; default is the jnp contraction (identical math).
    ``backend`` selects it through the kernel dispatcher instead
    ("auto"/"xla"/"pallas", op ``bin_gather``); an explicit
    ``bin_gather_op`` wins over ``backend``.

    Eager wrapper: ``backend`` resolves BEFORE the jitted impl traces, so
    an eager "auto" call genuinely benchmarks (the dispatcher never
    measures under an ambient trace).
    """
    if bin_gather_op is None and backend is not None:
        from repro.kernels import dispatch

        backend = dispatch.resolve(
            "bin_gather", backend, order=order, grid_shape=tuple(grid_shape),
            capacity=layout.slots.shape[1], dtype=str(pos.dtype), batch=batch,
        )
    return _gather_matrix_jit(
        pos, grid_padded, layout, grid_shape=tuple(grid_shape), order=order,
        stagger=stagger, guard=guard, bin_gather_op=bin_gather_op, backend=backend,
    )


def _fused_gather_xla_bins(d, padded_fields, *, grid_shape, order, guard):
    """Pure-XLA six-component gather: shared weights, per-component
    TRUE-support neighborhoods, (C, cap, 6) per-bin values."""
    n_cells, cap, _ = d.shape
    w_u = [sf.shape_weights(d[..., k], order, False) for k in range(3)]
    w_s = [sf.shape_weights(d[..., k], order, True) for k in range(3)]
    byz = {}  # four distinct wy (x) wz products over the six components
    comps = []
    for comp, stagger in enumerate(EB_STAGGERS):
        taps, bases = _taps_and_bases(order, stagger)
        tx, ty, tz = taps
        neigh = extract_neighborhoods(
            padded_fields[comp], grid_shape, taps=taps, bases=bases, guard=guard
        ).reshape(n_cells, tx, ty * tz)
        key = (stagger[1], stagger[2])
        if key not in byz:
            wy = w_s[1] if stagger[1] else w_u[1]
            wz = w_s[2] if stagger[2] else w_u[2]
            byz[key] = (wy[..., :, None] * wz[..., None, :]).reshape(n_cells, cap, ty * tz)
        wx = w_s[0] if stagger[0] else w_u[0]
        h = jnp.einsum("cpn,cmn->cpm", byz[key], neigh, precision=sf.CONTRACTION_PRECISION)
        comps.append(jnp.sum(wx * h, axis=-1))
    return jnp.stack(comps, axis=-1)  # (C, cap, 6)


def _fused_gather_pallas_bins(d, padded_fields, *, grid_shape, order, guard, fused_gather):
    """Pack the six neighborhoods on the unified window and run the
    Pallas megakernel: (C, cap, 6) per-bin values."""
    n_cells = d.shape[0]
    t, base = sf.unified_support(order)
    packed = jnp.stack(
        [
            extract_neighborhoods(
                f, grid_shape, taps=(t, t, t), bases=(base, base, base), guard=guard
            ).reshape(n_cells, t, t * t)
            for f in padded_fields
        ],
        axis=1,
    )  # (C, 6, T, T*T)
    return fused_gather(d, packed, order=order).astype(d.dtype)


def _fused_gather_bins_impl(d, padded_fields, *, grid_shape, order, guard, backend):
    from repro.kernels import dispatch

    name = dispatch.resolve(
        "gather_fused", backend, order=order, grid_shape=grid_shape,
        capacity=d.shape[1], dtype=str(d.dtype),
    )
    if name == "pallas":
        from repro.kernels.gather.ops import fused_bin_gather

        return _fused_gather_pallas_bins(
            d, padded_fields, grid_shape=grid_shape, order=order, guard=guard,
            fused_gather=fused_bin_gather,
        )
    return _fused_gather_xla_bins(d, padded_fields, grid_shape=grid_shape, order=order, guard=guard)


@partial(jax.jit, static_argnames=("grid_shape", "order", "guard", "backend"))
def _fused_gather_bins_jit(d, padded_fields, *, grid_shape, order, guard, backend):
    return _fused_gather_bins_impl(
        d, padded_fields, grid_shape=grid_shape, order=order, guard=guard, backend=backend
    )


def fused_gather_bins(d, padded_fields, *, grid_shape, order: int, guard: int | None = None, backend: str = "xla", batch: int = 1):
    """Post-slab fused gather: (C, cap, 3) offsets + six padded grids ->
    (C, cap, 6) per-bin field values via the named dispatcher backend.
    This is the portion of the hot path the gather backends disagree on —
    kernels.dispatch builds its gather_fused benchmark thunks on it.

    Eager wrapper: ``backend`` resolves BEFORE the jitted impl traces, so
    an eager "auto" call benchmarks real device execution (the dispatcher
    never measures under an ambient trace)."""
    from repro.kernels import dispatch

    g = sf.max_guard(order) if guard is None else guard
    name = dispatch.resolve(
        "gather_fused", backend, order=order, grid_shape=tuple(grid_shape),
        capacity=d.shape[1], dtype=str(d.dtype), batch=batch,
    )
    return _fused_gather_bins_jit(
        d, padded_fields, grid_shape=tuple(grid_shape), order=order, guard=g, backend=name
    )


@partial(jax.jit, static_argnames=("grid_shape", "order", "guard", "fused_gather", "backend"))
def _gather_fields_fused_jit(
    slab: BinSlab,
    padded_fields,
    layout: BinnedLayout,
    *,
    grid_shape,
    order: int,
    guard: int | None,
    fused_gather,
    backend: str | None,
):
    g = sf.max_guard(order) if guard is None else guard
    d = slab.d
    n_cells, cap = slab.valid.shape

    if fused_gather is not None:
        e_bins = _fused_gather_pallas_bins(
            d, padded_fields, grid_shape=grid_shape, order=order, guard=g,
            fused_gather=fused_gather,
        )
    elif backend is not None:
        e_bins = _fused_gather_bins_impl(
            d, padded_fields, grid_shape=grid_shape, order=order, guard=g, backend=backend
        )
    else:
        e_bins = _fused_gather_xla_bins(
            d, padded_fields, grid_shape=grid_shape, order=order, guard=g
        )

    # ONE scatter back to particle order for all six components (the
    # six-call path pays this slot-map gather per component); slots without
    # a particle are simply never read, unslotted particles read 0
    flat = e_bins.reshape(n_cells * cap, 6)
    pslot = layout.particle_slot
    vals = jnp.where(
        pslot[:, None] >= 0, flat[jnp.maximum(pslot, 0)], jnp.zeros((), flat.dtype)
    )
    return vals[:, :3], vals[:, 3:]


def gather_fields_fused(
    slab: BinSlab,
    padded_fields,
    layout: BinnedLayout,
    *,
    grid_shape,
    order: int,
    guard: int | None = None,
    fused_gather=None,
    backend: str | None = None,
    batch: int = 1,
):
    """All six Yee-staggered field components in one fused pass — the
    default ``gather="matrix"`` hot path (the dual of the fused
    three-component deposition).

    The slot-table position staging is NOT repeated here: ``slab`` is the
    step's one `BinSlab` (fractional offsets + validity, already in bin
    order) and must be consistent with ``layout`` and the positions the
    fields are gathered at. The six per-axis 1-D weight sets (centered +
    staggered per axis — every component uses one of the two variants per
    axis) are computed once and shared, the four distinct wy⊗wz tap
    products are reused across the component pairs that share them
    (Ey/Bz, Ez/By), and the six per-bin results scatter back to particle
    order through ONE slot-map gather.

    ``padded_fields``: the six guard-padded grids in `EB_STAGGERS` order
    (Ex, Ey, Ez, Bx, By, Bz).

    ``fused_gather`` is the packed slab -> (C, cap, 6) contraction:
    kernels.gather.fused_bin_gather (the Pallas megakernel — in-kernel
    weight build on the VPU + six shared-weight MXU contractions against
    one packed (C, 6, T, T·T) neighborhood tensor on the unified tap
    window, so the weight/byz operands never round-trip through HBM) or
    None for the pure-XLA reference, which contracts each component on its
    TRUE support (no padded FLOPs — XLA einsums pay for every zero) while
    still sharing the slab, the weights, and the byz products. Identical
    math either way. ``backend`` selects the route through the kernel
    dispatcher instead ("auto"/"xla"/"pallas", op ``gather_fused``); an
    explicit ``fused_gather`` callable wins over ``backend``.

    Eager wrapper: ``backend`` resolves BEFORE the jitted impl traces, so
    an eager "auto" call genuinely benchmarks (the dispatcher never
    measures under an ambient trace — the sim drivers, which trace this
    inside their step, prewarm the key at setup instead).

    Returns ``(e_p, b_p)``: (Np, 3) each, 0 for unslotted particles.
    """
    if fused_gather is None and backend is not None:
        from repro.kernels import dispatch

        backend = dispatch.resolve(
            "gather_fused", backend, order=order, grid_shape=tuple(grid_shape),
            capacity=slab.d.shape[1], dtype=str(slab.d.dtype), batch=batch,
        )
    return _gather_fields_fused_jit(
        slab, padded_fields, layout, grid_shape=tuple(grid_shape), order=order,
        guard=guard, fused_gather=fused_gather, backend=backend,
    )
