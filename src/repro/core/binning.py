"""Cell binning: the paper's ``GlobalSortParticlesByCell`` (counting sort).

The binned layout mirrors the paper's GPMA storage:

  slots:          (n_cells, capacity) int32 — particle index or INVALID (-1)
  particle_slot:  (n_particles,)       int32 — flat slot of each particle
                                               (INVALID if dead / overflowed)

Bins are rows; gaps (INVALID entries) are the GPMA's interspersed empty
slots. After a global sort the valid entries of row ``c`` are packed at the
front of the row and the particle *attribute arrays themselves* are permuted
into cell order (memory coherence, paper §4.4). Incremental updates
(gpma.py) only touch the index structure, never the attribute arrays.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.grad.permutations import slot_gather

INVALID = jnp.int32(-1)

# Trace-time counter: incremented every time `build_bin_slab` is traced.
# Tests trace a full pic_step and read the delta to assert structurally that
# the step stages the particle slab into bin order exactly ONCE (the BinSlab
# is shared between the fused field gather and the fused deposition).
SLAB_BUILDS = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BinnedLayout:
    """Functional GPMA index state (pytree)."""

    slots: jax.Array          # (n_cells, capacity) int32, particle id or -1
    particle_slot: jax.Array  # (n_particles,) int32, flat slot id or -1

    @property
    def n_cells(self) -> int:
        return self.slots.shape[0]

    @property
    def capacity(self) -> int:
        return self.slots.shape[1]

    def valid_mask(self) -> jax.Array:
        return self.slots >= 0

    def n_empty(self) -> jax.Array:
        return jnp.sum(self.slots < 0)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BinSlab:
    """Bin-resident particle staging slab (pytree), built ONCE per step.

    The slot-table gather of positions is the per-step staging cost every
    bin-based kernel used to pay separately (six gather_matrix calls plus
    the fused deposition each re-gathered `pos` into bin order). The slab
    stages it exactly once and both the fused six-component field gather
    and the fused deposition contract against it:

      d:      (n_cells, capacity, 3) fractional offsets pos - cell.
              Gap/overflow slots alias particle 0 — harmless, `valid`
              (for gather) or the zeroed value slab (for deposition)
              carries the masking.
      valid:  (n_cells, capacity) bool, True where the slot holds a
              particle.

    Velocity-dependent deposition values (q·w·v) are NOT part of the slab:
    they only exist after the push, and `bin_slab_values` gathers them
    against the same slot table when the deposition needs them.

    The slab is only consistent with a specific (positions, layout) pair;
    the simulation step rebuilds it right after the bin update (and the
    global sort rebuilds it after permuting attributes) and carries it in
    the simulation state, so the NEXT step's gather reuses the slab the
    deposition just consumed.
    """

    d: jax.Array
    valid: jax.Array


def build_bin_slab(pos, layout: BinnedLayout, *, grid_shape) -> BinSlab:
    """THE slot-table slab gather: stage positions into bin order once.

    Deliberately not jitted on its own — it inlines into the step trace so
    the SLAB_BUILDS counter sees every staging a traced step performs.
    """
    global SLAB_BUILDS
    SLAB_BUILDS += 1
    slots = layout.slots
    n_cells, _ = slots.shape
    valid = slots >= 0
    # slot_gather == pos[jnp.maximum(slots, 0)] bitwise, with a masked VJP so
    # reverse-mode through the slab never scatters alias cotangents onto
    # particle 0 (grad.permutations)
    pos_b = slot_gather(pos, slots)                  # (C, cap, 3) — once
    cells = cell_coords(n_cells, grid_shape)
    d = pos_b - cells[:, None, :].astype(pos.dtype)
    return BinSlab(d=d, valid=valid)


def bin_slab_staging(pos, vel, qw, layout: BinnedLayout, *, grid_shape):
    """Fused push-into-bin-order staging: positions AND the post-push q·w·v
    deposition values through ONE slot-table gather.

    `build_bin_slab` + `bin_slab_values` pay the slot gather twice (the
    PR 5 carried-forward follow-up); here the (N, 3) positions, (N, 3)
    velocities and (N,) values concatenate into one (N, 7) matrix so the
    row permutation runs once. Bit-identical to the two-gather route:
    `slot_gather` is pure row selection, so gathering a column-concatenated
    matrix yields exactly the per-array gathers column for column.

    Returns ``(BinSlab, values)`` with `values` the (n_cells, capacity, 3)
    q·w·v slab `bin_slab_values` would have produced.
    """
    global SLAB_BUILDS
    SLAB_BUILDS += 1
    slots = layout.slots
    n_cells, _ = slots.shape
    valid = slots >= 0
    packed = jnp.concatenate([pos, vel, qw[:, None]], axis=1)   # (N, 7)
    staged = slot_gather(packed, slots)                         # (C, cap, 7) — once
    cells = cell_coords(n_cells, grid_shape)
    d = staged[..., :3] - cells[:, None, :].astype(pos.dtype)
    qw_b = jnp.where(valid, staged[..., 6], jnp.zeros((), qw.dtype))
    vel_b = jnp.where(valid[..., None], staged[..., 3:6], jnp.zeros((), vel.dtype))
    return BinSlab(d=d, valid=valid), qw_b[..., None] * vel_b


def bin_slab_values(vel, qw, layout: BinnedLayout, slab: BinSlab) -> jax.Array:
    """Per-component deposition values q·w·v staged onto the slab's slot
    table: (n_cells, capacity, 3), exactly 0 on gap/overflow slots (the
    value slab carries the deposition masking)."""
    valid = slab.valid
    qw_b = jnp.where(valid, slot_gather(qw, layout.slots), jnp.zeros((), qw.dtype))
    vel_b = jnp.where(valid[..., None], slot_gather(vel, layout.slots), jnp.zeros((), vel.dtype))
    return qw_b[..., None] * vel_b


def cell_index(pos, grid_shape) -> jax.Array:
    """Flattened cell id for positions in grid units. pos: (..., 3)."""
    nx, ny, nz = grid_shape
    ix = jnp.clip(jnp.floor(pos[..., 0]).astype(jnp.int32), 0, nx - 1)
    iy = jnp.clip(jnp.floor(pos[..., 1]).astype(jnp.int32), 0, ny - 1)
    iz = jnp.clip(jnp.floor(pos[..., 2]).astype(jnp.int32), 0, nz - 1)
    return (ix * ny + iy) * nz + iz


def cell_coords(n_cells: int, grid_shape) -> jax.Array:
    """(n_cells, 3) integer coordinates of each flattened cell id."""
    nx, ny, nz = grid_shape
    c = jnp.arange(n_cells, dtype=jnp.int32)
    iz = c % nz
    iy = (c // nz) % ny
    ix = c // (ny * nz)
    return jnp.stack([ix, iy, iz], axis=-1)


def rank_in_runs(sorted_key) -> jax.Array:
    """Position of each entry within its run of equal keys: int32[n].

    ``sorted_key`` is sorted, so ``i - (index where i's run starts)`` is
    the rank, and that start is the running maximum of the run-start
    indices: one max-scan, no search and no gather. Equal, entry for
    entry, to ``arange(n) - searchsorted(sorted_key, sorted_key, "left")``
    for any sorted int keys.

    The scan doubles its reach each pass: log2(n) elementwise maxima of
    the array and its shifted copy, every op under the caller's scope.
    (``lax.cummax`` lowers to a reduce-window that the TPU compiler splits
    into ops with no scope, and takes tens of seconds to compile at 2M
    keys; ``lax.associative_scan`` compiles slower still.)
    """
    n = sorted_key.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    start = jnp.concatenate([jnp.ones((min(n, 1),), bool), sorted_key[1:] != sorted_key[:-1]])
    first = jnp.where(start, idx, 0)
    shift = 1
    while shift < n:
        first = jnp.maximum(first, jnp.pad(first[:-shift], (shift, 0)))
        shift *= 2
    return idx - first


@partial(jax.jit, static_argnames=("n_cells", "capacity"))
def build_bins(cell_ids, alive, *, n_cells: int, capacity: int):
    """Counting-sort rebuild of the binned layout: key argsort, then each
    particle's rank within its cell from a run-start max-scan
    (`rank_in_runs`).

    Dead particles (alive == False) get particle_slot = -1. Particles whose
    within-cell rank exceeds `capacity` overflow: they are left unslotted and
    counted, so the caller can grow capacity and retry (host-side).

    Returns (layout, overflow_count).
    """
    n = cell_ids.shape[0]
    key = jnp.where(alive, cell_ids, n_cells)  # dead -> sentinel bin
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    rank = rank_in_runs(sorted_key)

    in_range = (sorted_key < n_cells) & (rank < capacity)
    overflow = jnp.sum((sorted_key < n_cells) & (rank >= capacity))

    flat_slot = jnp.where(in_range, sorted_key.astype(jnp.int32) * capacity + rank, n_cells * capacity)
    slots = jnp.full((n_cells * capacity + 1,), INVALID)
    slots = slots.at[flat_slot].set(order.astype(jnp.int32))[:-1]
    particle_slot = jnp.full((n,), INVALID)
    particle_slot = particle_slot.at[order].set(jnp.where(in_range, flat_slot, INVALID).astype(jnp.int32))

    return BinnedLayout(slots=slots.reshape(n_cells, capacity), particle_slot=particle_slot), overflow


def sort_permutation(cell_ids, alive) -> jax.Array:
    """Permutation putting alive particles in cell order (the global sort's
    attribute permutation). Apply with tree_map(lambda a: a[perm], attrs)."""
    n = cell_ids.shape[0]
    key = jnp.where(alive, cell_ids, jnp.int32(2**30))
    return jnp.argsort(key, stable=True)


def choose_capacity(max_ppc: int, headroom: float = 1.5, multiple: int = 8) -> int:
    """Bin capacity with GPMA gap headroom, rounded to a lane-friendly multiple."""
    cap = int(max(1, max_ppc) * headroom) + 1
    return ((cap + multiple - 1) // multiple) * multiple
