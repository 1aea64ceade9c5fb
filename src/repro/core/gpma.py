"""Incremental particle sorting on a gapped binned layout (functional GPMA).

Paper §4.3: after the push, most particles stay in their cell (CFL), so a
full per-step sort is wasted work. The GPMA keeps the index array sorted
with gaps; only *moved* particles are deleted from their old bin and
inserted into a gap of the new bin. The paper's per-particle pointer ops are
O(1)-amortized on a sequential machine.

TPU adaptation (DESIGN.md §2): insert/delete become masked *vectorized*
updates over the whole tile. The expensive thing this avoids — exactly as in
the paper — is permuting the SoA attribute arrays (8+ streams of N_p values)
and re-establishing locality every step; the incremental path touches only
the int32 index structure. Rank assignment inside target bins uses one
key-only argsort (int32 keys, a counting-sort analogue) and a run-start
max-scan over the sorted keys (`binning.rank_in_runs`), never attribute
data. Bin-borrowing (paper's pointer-chasing fallback) is replaced by
rebuild-on-overflow, preserving the amortized bound under the same CFL
assumption.

All functions are jit-compatible; `GPMAStats` scalars feed the host-side
resort policy (resort_policy.py). The stages run under the device scopes
``pic.gpma.delete`` / ``.rank`` / ``.gaps`` / ``.insert`` (docs/sim_loop.md,
"Profiling a run").
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.binning import INVALID, BinnedLayout, rank_in_runs


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GPMAStats:
    """Per-step device-side statistics consumed by the resort policy."""

    n_moved: jax.Array       # particles that changed cell this step, PLUS
                             # previously-unslotted live particles whose
                             # insert landed (e.g. migrated-in arrivals on
                             # the distributed path) — a boundary crossing is
                             # one move no matter which shard observes it, so
                             # the moved-fraction perf proxy sees identical
                             # churn on every driver; particles stuck
                             # unslotted against a full bin are not recounted
    n_overflow: jax.Array    # inserts that found no gap (-> rebuild needed)
    n_empty: jax.Array       # empty slots after update
    n_alive: jax.Array       # live particles
    n_ranked: jax.Array      # keys the rank stage sorts and scans (all
                             # n: only the `n_moved` that changed cell need
                             # a rank, the rest sort to the sentinel key)


@partial(jax.jit, static_argnames=())
def gpma_update(layout: BinnedLayout, new_cell, alive):
    """Incrementally re-sort: delete moved particles from old bins, insert
    into gaps of their new bins.

    Args:
      layout: current binned layout (bins must reflect *pre-push* cells).
      new_cell: (n_particles,) int32 flattened cell ids after the push.
      alive: (n_particles,) bool.

    Returns:
      (new_layout, GPMAStats). Overflowed particles have particle_slot == -1
      and are NOT represented in any bin — if stats.n_overflow > 0 the caller
      must rebuild (resort policy makes this mandatory, as in the paper).
    """
    n_cells, cap = layout.slots.shape
    n = new_cell.shape[0]
    flat = layout.slots.reshape(-1)

    with jax.named_scope("pic.gpma.delete"):
        old_slot = layout.particle_slot
        had_slot = old_slot >= 0
        old_cell = jnp.where(had_slot, old_slot // cap, -1)

        moved = alive & had_slot & (new_cell != old_cell)
        died = (~alive) & had_slot
        needs_insert = alive & (new_cell != old_cell)  # moved or previously unslotted

        # --- Stage "delete": free old slots of moved + dead particles (O(1) scatter).
        free_src = moved | died
        dump = n_cells * cap  # scatter sink
        flat = jnp.concatenate([flat, jnp.zeros((1,), flat.dtype)])
        flat = flat.at[jnp.where(free_src, old_slot, dump)].set(INVALID)
        flat = flat[:-1]
        slots = flat.reshape(n_cells, cap)

    with jax.named_scope("pic.gpma.rank"):
        # --- Stage "insert": rank pending moves within their target bin.
        key = jnp.where(needs_insert, new_cell, n_cells)
        order = jnp.argsort(key, stable=True)            # key-only sort (index data)
        sorted_key = key[order]
        rank = rank_in_runs(sorted_key)

    with jax.named_scope("pic.gpma.gaps"):
        # r-th gap of each bin (stable argsort over the small capacity axis).
        free_mask = slots < 0
        free_order = jnp.argsort(~free_mask, axis=1, stable=True)  # (n_cells, cap)
        n_free = jnp.sum(free_mask, axis=1)

    with jax.named_scope("pic.gpma.insert"):
        tgt = jnp.minimum(sorted_key, n_cells - 1).astype(jnp.int32)
        is_insert = sorted_key < n_cells
        fits = is_insert & (rank < n_free[tgt])
        dst = tgt * cap + free_order[tgt, jnp.minimum(rank, cap - 1)]
        dst = jnp.where(fits, dst, dump)

        flat = jnp.concatenate([slots.reshape(-1), jnp.zeros((1,), flat.dtype)])
        flat = flat.at[dst].set(order.astype(jnp.int32))
        flat = flat[:-1]
        slots = flat.reshape(n_cells, cap)

        # --- particle_slot bookkeeping.
        pslot = jnp.where(free_src, INVALID, old_slot)
        upd = jnp.where(fits, dst, INVALID).astype(jnp.int32)
        pslot = pslot.at[order].set(jnp.where(is_insert, upd, pslot[order]))

        # An unslotted live particle counts as a move only when its insert LANDS
        # (a migrated-in arrival binning for the first time, or an overflow
        # straggler finally finding room) — a stationary particle stuck at
        # particle_slot == -1 against a full bin must not inflate the churn
        # proxy on every step it waits. Known bounded overcount: a crossing
        # whose insert stalls is counted at the crossing (`moved`) AND at the
        # eventual landing — per-particle "already counted" memory isn't worth
        # carrying, this only arises where overflow is tolerated across steps
        # (needs_bins=False ablation configs; bin-based configs mandatory-sort
        # the same step), and the bias direction (earlier sorts) is safe.
        landed = jnp.zeros((n,), bool).at[order].set(fits)
        stats = GPMAStats(
            n_moved=jnp.sum(moved) + jnp.sum(landed & ~had_slot),
            n_overflow=jnp.sum(is_insert & ~fits),
            n_empty=jnp.sum(slots < 0),
            n_alive=jnp.sum(alive),
            n_ranked=jnp.int32(n),
        )
    return BinnedLayout(slots=slots, particle_slot=pslot), stats
