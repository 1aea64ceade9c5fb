"""Pure-jnp oracle for the segment accumulation kernel."""

import jax.numpy as jnp

from repro.core.shape_functions import CONTRACTION_PRECISION


def segment_accumulate_ref(w, u):
    """out[v] = sum_c w[v,c] * u[v,c,:]."""
    return jnp.einsum(
        "vc,vcd->vd", w, u, preferred_element_type=jnp.float32,
        precision=CONTRACTION_PRECISION,
    ).astype(u.dtype)
