"""Generalized Matrix-PIC scatter-add: sort -> bin -> dense accumulate.

The paper's Appendix B argues the co-design applies to any
"sparse sources -> dense target" accumulation. In the LM stack that pattern
is the embedding-table gradient and the MoE combine. This module provides
the generic op, built from the same three stages as the deposition kernel:

  stage 1 (sort):    counting-sort indices into a (n_bins, capacity) layout
                     with gaps (binning.build_bins);
  stage 2 (matrix):  per-bin accumulation as a batched (w^T U) contraction
                     over the capacity axis — the MXU-mapped MOPA analogue;
  stage 3 (overflow):the few items that exceed bin capacity fall back to a
                     plain scatter-add (exact), mirroring the paper's
                     low-density fallback recommendation (§6.1).

`matrix_scatter_add` is exact for any input; capacity only trades the dense/
fallback split.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.shape_functions import CONTRACTION_PRECISION


@partial(jax.jit, static_argnames=("n_bins", "capacity"))
def matrix_scatter_add(indices, updates, *, n_bins: int, capacity: int, weights=None):
    """out[v] = sum_{i: indices[i]==v} weights[i] * updates[i].

    Args:
      indices: (T,) int32 bin ids in [0, n_bins) (negative = dropped).
      updates: (T, D).
      capacity: bin capacity for the dense path.
      weights: optional (T,) scale per item.

    Returns: (n_bins, D), dtype of updates.
    """
    t = indices.shape[0]
    alive = indices >= 0
    safe_idx = jnp.where(alive, indices, n_bins - 1)

    # --- stage 1: counting sort into gapped bins (key-only argsort).
    key = jnp.where(alive, safe_idx, n_bins)
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    first = jnp.searchsorted(sorted_key, sorted_key, side="left")
    rank = (jnp.arange(t) - first).astype(jnp.int32)
    in_dense = (sorted_key < n_bins) & (rank < capacity)

    # gather updates into the binned layout (gaps stay zero); items outside
    # the dense set go to a dump slot so .set() never collides.
    dump = n_bins * capacity
    dst = jnp.where(in_dense, sorted_key.astype(jnp.int32) * capacity + rank, dump)
    w = jnp.ones((t,), updates.dtype) if weights is None else weights.astype(updates.dtype)

    binned_u = jnp.zeros((n_bins * capacity + 1, updates.shape[1]), updates.dtype)
    binned_u = binned_u.at[dst].set(updates[order])[:-1].reshape(n_bins, capacity, -1)
    binned_w = jnp.zeros((n_bins * capacity + 1,), updates.dtype)
    binned_w = binned_w.at[dst].set(w[order])[:-1].reshape(n_bins, capacity)

    # --- stage 2: dense per-bin contraction (batched 1 x cap @ cap x D).
    out = jnp.einsum("bc,bcd->bd", binned_w, binned_u, precision=CONTRACTION_PRECISION)

    # --- stage 3: exact overflow fallback (rare when capacity is sized
    # like the GPMA headroom; measured in tests/benchmarks).
    overflow = (sorted_key < n_bins) & (rank >= capacity)
    of_idx = jnp.where(overflow, sorted_key, n_bins).astype(jnp.int32)
    of_upd = jnp.where(overflow[:, None], (w[order])[:, None] * updates[order], jnp.zeros((), updates.dtype))
    out_ext = jnp.concatenate([out, jnp.zeros((1, out.shape[1]), out.dtype)])
    out_ext = out_ext.at[of_idx].add(of_upd)
    return out_ext[:-1]


def scatter_add_ref(indices, updates, *, n_bins: int, weights=None):
    """Plain scatter-add oracle."""
    alive = indices >= 0
    w = jnp.ones(indices.shape, updates.dtype) if weights is None else weights.astype(updates.dtype)
    upd = jnp.where(alive[:, None], w[:, None] * updates, jnp.zeros((), updates.dtype))
    idx = jnp.where(alive, indices, n_bins)
    out = jnp.zeros((n_bins + 1, updates.shape[1]), updates.dtype)
    return out.at[idx].add(upd)[:-1]
