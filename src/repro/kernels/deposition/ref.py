"""Pure-jnp oracles for the binned deposition kernels.

Both oracles share their shape-weight evaluation with the Pallas kernel
bodies through `shape_functions.shape_weights_window` — the kernel and the
reference differ only in who runs the contraction (MXU dot vs einsum).
"""

import jax.numpy as jnp

from repro.core.shape_functions import (
    CONTRACTION_PRECISION,
    shape_weights_window,
    unified_support,
)


def bin_outer_product_ref(a, b):
    """out[c] = A_c^T @ B_c. a: (C, cap, M), b: (C, cap, N) -> (C, M, N)."""
    return jnp.einsum(
        "cpm,cpn->cmn", a, b, preferred_element_type=jnp.float32,
        precision=CONTRACTION_PRECISION,
    )


def fused_bin_deposit_ref(d, val, *, order: int):
    """Oracle for the fused three-component megakernel.

    d, val: (C, cap, 3) -> (C, 3, T, T*T) float32 packed rhocell tiles on
    the unified tap window of ``order`` (component k staggered on axis k).
    """
    t, base = unified_support(order)
    c, cap, _ = d.shape
    packed = []
    for comp in range(3):
        wx = shape_weights_window(d[..., 0], order, comp == 0, n_taps=t, base=base)
        wy = shape_weights_window(d[..., 1], order, comp == 1, n_taps=t, base=base)
        wz = shape_weights_window(d[..., 2], order, comp == 2, n_taps=t, base=base)
        a = wx * val[..., comp][..., None]
        byz = (wy[..., :, None] * wz[..., None, :]).reshape(c, cap, t * t)
        packed.append(jnp.einsum(
            "cpm,cpn->cmn", a, byz, preferred_element_type=jnp.float32,
            precision=CONTRACTION_PRECISION,
        ))
    return jnp.stack(packed, axis=1)


def fused_bin_deposit_reduced_ref(d, val, *, order: int, grid_shape, guard: int):
    """Oracle for the epilogue-fused megakernel: the packed oracle followed
    by reduce_rhocell_separable's z pass, per column.

    Returns (nx*ny, 3, nz+2g, T, T) float32.
    """
    nx, ny, nz = grid_shape
    g = guard
    t, base = unified_support(order)
    packed = fused_bin_deposit_ref(d, val, order=order)  # (C, 3, T, T*T)
    rho = packed.reshape(nx * ny, nz, 3, t, t, t)
    acc = jnp.zeros((nx * ny, 3, nz + 2 * g, t, t), packed.dtype)
    for c in range(t):
        acc = acc.at[:, :, g + base + c : g + base + c + nz].add(
            jnp.moveaxis(rho[..., c], 1, 2)
        )
    return acc
