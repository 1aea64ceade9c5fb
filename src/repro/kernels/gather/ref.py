"""Pure-jnp oracles for the binned gather kernels."""

import jax.numpy as jnp

from repro.core.gather import EB_STAGGERS
from repro.core.shape_functions import CONTRACTION_PRECISION, packed_axis_weights


def bin_gather_ref(wx, byz, g):
    """e[c,p] = sum_{m,n} wx[c,p,m] byz[c,p,n] g[c,m,n]."""
    h = jnp.einsum(
        "cpn,cmn->cpm", byz, g, preferred_element_type=jnp.float32,
        precision=CONTRACTION_PRECISION,
    )
    return jnp.sum(wx * h, axis=-1)


def fused_bin_gather_ref(d, g, *, order: int):
    """Oracle for the fused six-component gather megakernel: identical math
    (in-kernel weight build included) on the packed unified-window operands.

    d: (C, cap, 3) slab offsets; g: (C, 6, T, T*T) packed neighborhoods.
    Returns (C, cap, 6) float32 in EB_STAGGERS order.
    """
    w = packed_axis_weights(d, order)
    outs = []
    for comp, stagger in enumerate(EB_STAGGERS):
        wy = w[(1, stagger[1])]
        wz = w[(2, stagger[2])]
        byz = (wy[..., :, None] * wz[..., None, :]).reshape(d.shape[0], d.shape[1], -1)
        h = jnp.einsum(
            "cpn,cmn->cpm", byz, g[:, comp], preferred_element_type=jnp.float32,
            precision=CONTRACTION_PRECISION,
        )
        outs.append(jnp.sum(w[(0, stagger[0])] * h, axis=-1))
    return jnp.stack(outs, axis=-1)
