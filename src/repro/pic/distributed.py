"""Domain-decomposed PIC with shard_map (the paper's per-MPI-rank design
mapped to TPU collectives).

Decomposition: grid x over the 'data' mesh axis (optionally ('pod','data')),
grid y over 'model', z kept local (periodic inside the shard). Per step,
entirely inside one jitted shard_map:

  1. field halo extension    — ppermute slab exchange (ICI-neighbor traffic,
                               the analogue of MPI_Sendrecv halos)
  2. gather + Boris push     — local
  3. particle migration      — dimension-by-dimension bounded-buffer
                               ppermute (corners route x-then-y), the
                               analogue of MPI particle exchange
  4. GPMA incremental sort   — local per-shard bins (paper: per-rank GPMA)
  5. deposition              — local; guard contributions reduced onto
                               neighbors with the reverse slab exchange
  6. Maxwell update          — slice-based curls on 1-cell halos

Buffers are fixed-size (`mig_cap`); overflow is *counted* and surfaced so a
production driver can grow buffers — nothing happens silently:

* send-side overflow (`mig_send_overflow`): a particle left its shard but no
  exchange-buffer slot was free. It stays resident with an out-of-range
  local position, is masked out of binning/gather/push/deposition for the
  step (garbage shape weights from raw out-of-range coordinates would
  otherwise corrupt the boundary current), and retries migration on the next
  step. Retryable; `stats["n_unmigrated"]` counts the currently-frozen ones.
* receive-side overflow (`mig_recv_dropped`): the destination shard had no
  dead slot left, so the particle was DESTROYED (charge loss). The windowed
  driver (pic/dist_simulation.py) treats a nonzero drop count as a
  halt-and-grow event — the offending step is discarded and re-run after the
  host grows the per-shard particle arrays — so no run driven by
  `DistSimulation` ever loses charge this way.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import (
    build_bin_slab,
    build_bins,
    cell_index,
    deposit_current_matrix_fused,
    deposit_matrix,
    gather_fields_fused,
    gather_matrix,
    gpma_update,
    sort_permutation,
)
from repro.core.binning import BinnedLayout, BinSlab, bin_slab_staging
from repro.distributed.comm import CommSpec
from repro.distributed.compression import (
    MIG_ROW_BYTES_COMPRESSED,
    MIG_ROW_BYTES_EXACT,
    pack_momenta,
    pack_positions,
    unpack_momenta,
    unpack_positions,
)
from repro.pic.grid import B_STAGGER, E_STAGGER, GridSpec
from repro.pic.maxwell import curl_b_padded, curl_e_padded
from repro.pic.plasma import ParticleState
from repro.pic.pusher import advance_positions, boris_push, lorentz_gamma
from repro.core.shape_functions import max_guard


# ---------------------------------------------------------------------------
# collective helpers (inside shard_map)
# ---------------------------------------------------------------------------

def _ring(axis_name, shift):
    n = jax.lax.axis_size(axis_name)
    if shift == +1:
        return [(i, (i + 1) % n) for i in range(n)]
    return [((i + 1) % n, i) for i in range(n)]


def halo_extend(f, g: int, axis: int, axis_name):
    """Extend f by g cells on both sides of `axis` using neighbor slabs."""
    n = f.shape[axis]
    lo = lax.slice_in_dim(f, 0, g, axis=axis)
    hi = lax.slice_in_dim(f, n - g, n, axis=axis)
    from_prev = lax.ppermute(hi, axis_name, _ring(axis_name, +1))
    from_next = lax.ppermute(lo, axis_name, _ring(axis_name, -1))
    return jnp.concatenate([from_prev, f, from_next], axis=axis)


def halo_extend_periodic_local(f, g: int, axis: int):
    """Local periodic extension (for the undecomposed z axis)."""
    n = f.shape[axis]
    lo = lax.slice_in_dim(f, 0, g, axis=axis)
    hi = lax.slice_in_dim(f, n - g, n, axis=axis)
    return jnp.concatenate([hi, f, lo], axis=axis)


def halo_reduce(fpad, g: int, axis: int, axis_name):
    """Fold guard contributions of a padded array onto the neighbors' cores
    (reverse of halo_extend): returns array shrunk by 2g along `axis`."""
    n = fpad.shape[axis] - 2 * g
    lo_guard = lax.slice_in_dim(fpad, 0, g, axis=axis)
    hi_guard = lax.slice_in_dim(fpad, g + n, g + n + g, axis=axis)
    core = lax.slice_in_dim(fpad, g, g + n, axis=axis)
    from_prev_hi = lax.ppermute(hi_guard, axis_name, _ring(axis_name, +1))
    from_next_lo = lax.ppermute(lo_guard, axis_name, _ring(axis_name, -1))
    core = jnp.moveaxis(core, axis, 0)
    core = core.at[:g].add(jnp.moveaxis(from_prev_hi, axis, 0))
    core = core.at[n - g :].add(jnp.moveaxis(from_next_lo, axis, 0))
    return jnp.moveaxis(core, 0, axis)


def halo_reduce_periodic_local(fpad, g: int, axis: int):
    n = fpad.shape[axis] - 2 * g
    lo = lax.slice_in_dim(fpad, 0, g, axis=axis)
    hi = lax.slice_in_dim(fpad, g + n, g + n + g, axis=axis)
    core = lax.slice_in_dim(fpad, g, g + n, axis=axis)
    core = jnp.moveaxis(core, axis, 0)
    core = core.at[:g].add(jnp.moveaxis(hi, axis, 0))
    core = core.at[n - g :].add(jnp.moveaxis(lo, axis, 0))
    return jnp.moveaxis(core, 0, axis)


# ---------------------------------------------------------------------------
# overlapped halo exchange (comm co-design)
# ---------------------------------------------------------------------------
#
# The serialized `_extend_all`/`_reduce_all` chain the per-axis exchanges:
# the y ppermute slices slabs out of the x-extended array, so it cannot
# issue until the x exchange has landed. The overlapped variants below
# re-express the SAME region map so every first-hop ppermute slices the raw
# local block — the compiler is free to issue the x slabs, the y slabs and
# the interior compute concurrently and hide the boundary traffic behind
# the bulk. ppermute is pure routing (no arithmetic), and the reduce keeps
# the serialized per-element float ADD GROUPING, so both variants are
# bitwise identical to the serialized path (asserted by tier-1 and the
# comm benchmark's --smoke lane).

def halo_extend_overlapped(f, g: int, x_axis, y_axis):
    """Extend f by g cells along x AND y in one concurrent exchange round.

    Edge slabs slice the raw block; the four g×g corners route x-then-y as
    two-hop ppermutes of just the corner block (the serialized path ships
    them embedded in the second-axis slabs — same values, same route, less
    serialization). The z periodic extension is applied by the caller LAST,
    matching the serialized x → y → z order.
    """
    nx, ny = f.shape[0], f.shape[1]
    # first-hop slabs, all sliced from the raw local block: no exchange
    # depends on another exchange's result
    row_top = lax.ppermute(f[nx - g:], x_axis, _ring(x_axis, +1))
    row_bot = lax.ppermute(f[:g], x_axis, _ring(x_axis, -1))
    col_left = lax.ppermute(f[:, ny - g:], y_axis, _ring(y_axis, +1))
    col_right = lax.ppermute(f[:, :g], y_axis, _ring(y_axis, -1))
    # corners: g×g two-hop blocks, x hop then y hop (the serialized routing)
    hop_x = lambda blk, s: lax.ppermute(blk, x_axis, _ring(x_axis, s))
    hop_y = lambda blk, s: lax.ppermute(blk, y_axis, _ring(y_axis, s))
    c_tl = hop_y(hop_x(f[nx - g:, ny - g:], +1), +1)
    c_tr = hop_y(hop_x(f[nx - g:, :g], +1), -1)
    c_bl = hop_y(hop_x(f[:g, ny - g:], -1), +1)
    c_br = hop_y(hop_x(f[:g, :g], -1), -1)
    top = jnp.concatenate([c_tl, row_top, c_tr], axis=1)
    mid = jnp.concatenate([col_left, f, col_right], axis=1)
    bot = jnp.concatenate([c_bl, row_bot, c_br], axis=1)
    return jnp.concatenate([top, mid, bot], axis=0)


def halo_reduce_overlapped(zf, g: int, x_axis, y_axis):
    """Fold x and y guard contributions onto neighbor cores in one
    concurrent exchange round. `zf` is the padded deposition grid AFTER the
    caller's local z fold ((nx+2g, ny+2g, nz)); returns the (nx, ny, nz)
    core.

    Bit-identity with the serialized z → y → x fold hinges on float add
    grouping: the serialized x-phase ships guard rows whose corner columns
    ALREADY hold the received-y contribution, so the four corner-mixed g×g
    pieces here are summed BEFORE their x hop — every destination element
    sees exactly the serialized (zf + recv_y) + recv_x association. The
    full-height y slabs and the pure middle x slabs are first-hop reads of
    `zf` and issue concurrently. Requires nx, ny >= 2g (the pure-middle
    column split is empty or negative below that); `_reduce_all` falls back
    to the serialized fold for smaller shards.
    """
    nx = zf.shape[0] - 2 * g
    ny = zf.shape[1] - 2 * g
    # full-height y-guard slabs: first hop, issues immediately
    recv_y_hi = lax.ppermute(zf[:, ny + g:], y_axis, _ring(y_axis, +1))
    recv_y_lo = lax.ppermute(zf[:, :g], y_axis, _ring(y_axis, -1))
    # pure-middle x-guard rows (columns untouched by the y fold): first hop
    recv_x_hi_mid = lax.ppermute(zf[nx + g:, 2 * g:ny], x_axis, _ring(x_axis, +1))
    recv_x_lo_mid = lax.ppermute(zf[:g, 2 * g:ny], x_axis, _ring(x_axis, -1))
    # corner-mixed g×g pieces: zf corner + received y contribution summed
    # pre-send — the exact partial sums the serialized x-phase transports
    hi_l = zf[nx + g:, g:2 * g] + recv_y_hi[nx + g:]
    hi_r = zf[nx + g:, ny:ny + g] + recv_y_lo[nx + g:]
    lo_l = zf[:g, g:2 * g] + recv_y_hi[:g]
    lo_r = zf[:g, ny:ny + g] + recv_y_lo[:g]
    recv_x_hi = jnp.concatenate([
        lax.ppermute(hi_l, x_axis, _ring(x_axis, +1)),
        recv_x_hi_mid,
        lax.ppermute(hi_r, x_axis, _ring(x_axis, +1)),
    ], axis=1)
    recv_x_lo = jnp.concatenate([
        lax.ppermute(lo_l, x_axis, _ring(x_axis, -1)),
        recv_x_lo_mid,
        lax.ppermute(lo_r, x_axis, _ring(x_axis, -1)),
    ], axis=1)
    # destination adds in the serialized order: interior, +y, +x
    out = zf[g:nx + g, g:ny + g]
    out = out.at[:, :g].add(recv_y_hi[g:nx + g])
    out = out.at[:, ny - g:].add(recv_y_lo[g:nx + g])
    out = out.at[:g].add(recv_x_hi)
    out = out.at[nx - g:].add(recv_x_lo)
    return out


# ---------------------------------------------------------------------------
# particle migration
# ---------------------------------------------------------------------------

def _pack(mask, arrays, cap: int):
    """Pack masked rows into a fixed-size buffer. Returns (bufs, valid,
    selected_mask, n_overflow)."""
    order = jnp.argsort(~mask, stable=True)
    sel = order[:cap]
    valid = mask[sel]
    bufs = [a[sel] for a in arrays]
    selected = jnp.zeros_like(mask).at[sel].set(valid)
    n_overflow = jnp.sum(mask) - jnp.sum(valid)
    return bufs, valid, selected, n_overflow


def _insert(parts_arrays, alive, bufs, valid):
    """Insert buffer rows into dead slots. Returns updated arrays + alive +
    the count of received particles that found no dead slot (DESTROYED —
    the caller must surface this as `mig_recv_dropped`, never fold it into a
    retryable counter) + the boolean mask of indices that received an
    arrival (consumers must count arrivals as cell *moves*: an arrival may
    reuse a just-departed index whose stale `particle_slot` happens to map
    the arrival's own cell, which makes it invisible to GPMA churn stats)."""
    free_order = jnp.argsort(alive, stable=True)  # dead (False) first
    nbuf = valid.shape[0]
    dst = free_order[:nbuf]
    can = ~alive[dst] & valid
    n_dropped = jnp.sum(valid) - jnp.sum(can)
    dump = alive.shape[0]
    dst_safe = jnp.where(can, dst, dump)
    out = []
    for cur, buf in zip(parts_arrays, bufs):
        ext = jnp.concatenate([cur, jnp.zeros((1,) + cur.shape[1:], cur.dtype)])
        out.append(ext.at[dst_safe].set(buf)[:-1])
    alive_ext = jnp.concatenate([alive, jnp.zeros((1,), bool)])
    alive = alive_ext.at[dst_safe].set(True)[:-1]
    inserted = jnp.zeros((alive.shape[0] + 1,), bool).at[dst_safe].set(can)[:-1]
    return out, alive, n_dropped, inserted


def migrate_axis(pos, u, w, alive, *, coord: int, extent: int, axis_name, mig_cap: int,
                 local_shape=None, compress: bool = False):
    """Exchange out-of-range particles along one decomposed axis.

    Returns ``(pos, u, w, alive, n_send_overflow, n_recv_dropped,
    arrived)``: send-side overflow is retryable (the particle stays
    resident, out-of-range, and must be masked from binning/deposition
    until it migrates); receive-side drops are destroyed particles;
    ``arrived`` is the boolean mask of indices that received a migrated-in
    particle this call (for churn accounting — see `_insert`).

    ``compress`` (``comm.compress_migration``) quantizes the exchange
    payload on the wire: positions are shard-relative after the coordinate
    shift below, so they pack into margin-banded uint16 fixed point over
    the local extent (``local_shape`` required) and momenta into bfloat16;
    weights cross exact, so total charge is conserved bit-for-bit. Packing
    happens BEFORE the ppermutes and unpacking after — the collective
    itself carries 16 B/row instead of 28 B (see distributed/compression
    for the tolerance contract). Invalid buffer rows round-trip through
    garbage values harmlessly: `_insert` never lands them.
    """
    x = pos[:, coord]
    go_hi = alive & (x >= extent)
    go_lo = alive & (x < 0)

    bufs_hi, valid_hi, sel_hi, of_hi = _pack(go_hi, [pos, u, w], mig_cap)
    bufs_lo, valid_lo, sel_lo, of_lo = _pack(go_lo, [pos, u, w], mig_cap)
    # shift coordinates into the receiver's local frame
    bufs_hi[0] = bufs_hi[0].at[:, coord].add(-float(extent))
    bufs_lo[0] = bufs_lo[0].at[:, coord].add(float(extent))

    alive = alive & ~(sel_hi | sel_lo)

    if compress:
        pack = lambda b: [pack_positions(b[0], local_shape), pack_momenta(b[1]), b[2]]
        bufs_hi, bufs_lo = pack(bufs_hi), pack(bufs_lo)

    recv_from_prev = [lax.ppermute(b, axis_name, _ring(axis_name, +1)) for b in bufs_hi]
    recv_valid_prev = lax.ppermute(valid_hi, axis_name, _ring(axis_name, +1))
    recv_from_next = [lax.ppermute(b, axis_name, _ring(axis_name, -1)) for b in bufs_lo]
    recv_valid_next = lax.ppermute(valid_lo, axis_name, _ring(axis_name, -1))

    if compress:
        unpack = lambda b: [
            unpack_positions(b[0], local_shape, pos.dtype),
            unpack_momenta(b[1], u.dtype),
            b[2],
        ]
        recv_from_prev, recv_from_next = unpack(recv_from_prev), unpack(recv_from_next)

    arrays = [pos, u, w]
    arrays, alive, drop1, ins1 = _insert(arrays, alive, recv_from_prev, recv_valid_prev)
    arrays, alive, drop2, ins2 = _insert(arrays, alive, recv_from_next, recv_valid_next)
    pos, u, w = arrays
    return pos, u, w, alive, of_hi + of_lo, drop1 + drop2, ins1 | ins2


# ---------------------------------------------------------------------------
# distributed step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistConfig:
    local_grid: GridSpec          # per-shard block
    dt: float
    order: int = 1
    deposition: str = "matrix"    # matrix (fused megakernel) | matrix_unfused
    gather: str = "matrix"        # matrix (fused six-component) | matrix_unfused
    backend: str = "auto"         # kernel-dispatch backend for the bin
                                  # contractions: auto | xla | pallas |
                                  # pallas_reduced
    charge: float = -1.0
    mass: float = 1.0
    capacity: int = 16
    mig_cap: int = 256
    x_axes: tuple = ("data",)     # mesh axes decomposing grid x
    y_axes: tuple = ("model",)
    comm: CommSpec = CommSpec()   # communication co-design knobs

    def __post_init__(self):
        validate_shard_guard(self.local_grid, self.order)
        if self.deposition not in ("matrix", "matrix_unfused"):
            raise ValueError(
                f"DistConfig.deposition must be 'matrix' or 'matrix_unfused', got {self.deposition!r} "
                "(the distributed step is bin-based; scatter/rhocell modes are single-device only)"
            )
        if self.gather not in ("matrix", "matrix_unfused"):
            raise ValueError(
                f"DistConfig.gather must be 'matrix' or 'matrix_unfused', got {self.gather!r} "
                "(the distributed step gathers through the bins; scatter gather is single-device only)"
            )

    @property
    def guard(self) -> int:
        return max_guard(self.order)

    @property
    def needs_slab(self) -> bool:
        """Whether the step rebuilds the carried `BinSlab` (a fused kernel
        consumes it). The slab arrays are always carried — the shard_map
        specs stay config-independent — but pure-unfused ablation configs
        pass them through untouched."""
        return self.deposition == "matrix" or self.gather == "matrix"


def validate_shard_guard(local_grid: GridSpec, order: int) -> None:
    """Fail loudly when the guard width exceeds the local shard extent.

    `halo_extend`/`halo_reduce` slice a g-cell slab off each side of the
    LOCAL block and exchange it with the ring neighbors. With
    g > local extent the sliced slab silently wraps into the neighbor's
    neighbor (the slice covers the whole block and then some), producing
    wrong fields/currents with no error. Shards must be at least
    `max_guard(order)` cells wide along every decomposed axis (and z, whose
    local periodic extension slices the same slabs).
    """
    g = max_guard(order)
    smallest = min(local_grid.shape)
    if g > smallest:
        raise ValueError(
            f"guard width {g} (deposition order {order}) exceeds the smallest local shard "
            f"extent {smallest} (local grid {local_grid.shape}): halo slabs would wrap into "
            f"the neighbor's neighbor. Use shards of at least {g} cells per axis — at order "
            f"{order} that means local_grid.shape >= ({g}, {g}, {g})."
        )


def _overlap_ok(cfg: DistConfig) -> bool:
    """Static predicate: the overlapped exchange handles exactly one mesh
    axis per grid dimension (multi-axis decompositions chain by nature)."""
    return cfg.comm.overlap_halo and len(cfg.x_axes) == 1 and len(cfg.y_axes) == 1


def _extend_all(f, g, cfg: DistConfig):
    if _overlap_ok(cfg):
        f = halo_extend_overlapped(f, g, cfg.x_axes[0], cfg.y_axes[0])
        return halo_extend_periodic_local(f, g, 2)
    for ax_name in cfg.x_axes:
        f = halo_extend(f, g, 0, ax_name)
    for ax_name in cfg.y_axes:
        f = halo_extend(f, g, 1, ax_name)
    return halo_extend_periodic_local(f, g, 2)


def _reduce_all(fpad, g, cfg: DistConfig):
    fpad = halo_reduce_periodic_local(fpad, g, 2)
    nx, ny = cfg.local_grid.shape[0], cfg.local_grid.shape[1]
    if _overlap_ok(cfg) and nx >= 2 * g and ny >= 2 * g:
        return halo_reduce_overlapped(fpad, g, cfg.x_axes[0], cfg.y_axes[0])
    for ax_name in reversed(cfg.y_axes):
        fpad = halo_reduce(fpad, g, 1, ax_name)
    for ax_name in reversed(cfg.x_axes):
        fpad = halo_reduce(fpad, g, 0, ax_name)
    return fpad


def in_domain(pos, shape):
    """Particles whose local position lies inside this shard's block on the
    decomposed axes (z is locally periodic and always in range after the
    per-step wrap). Send-side migration overflow leaves particles resident
    with out-of-range coordinates; everything bin- or weight-based must mask
    on this — `cell_index` would clip them into the boundary cell and the
    raw out-of-range offsets produce garbage shape weights."""
    x, y = pos[:, 0], pos[:, 1]
    return (x >= 0) & (x < shape[0]) & (y >= 0) & (y < shape[1])


def resolve_sharded_backend(cfg: DistConfig) -> DistConfig:
    """Bake ``cfg.backend`` into a concrete dispatcher name for shard_map
    use. ``pallas_call`` has no shard_map replication rule, so the Pallas
    backends are unavailable inside the shard body (``sharded=True`` key
    axis) and both "auto" and a forced Pallas name resolve to "xla" — with
    no benchmark, and eagerly, at build time: the shard body then traces
    with the concrete name only. Every builder that traces
    `dist_pic_step_local` must go through this."""
    from repro.kernels import dispatch

    name = dispatch.resolve(
        dispatch.OP_BY_DEPOSITION[cfg.deposition], cfg.backend,
        order=cfg.order, grid_shape=cfg.local_grid.shape,
        capacity=cfg.capacity, sharded=True,
    )
    return dataclasses.replace(cfg, backend=name)


def dist_pic_step_local(fields, pos, u, w, alive, slots, particle_slot, slab_d, slab_valid, cfg: DistConfig,
                        *, mid_pos=None, mid_u=None, use_mid=None):
    """Body executed per shard inside shard_map. fields: 6-tuple of local
    blocks; particle arrays local; ``slab_d``/``slab_valid`` the carried
    `BinSlab` arrays (consistent with the incoming bins — rebuilt below
    right after the bin update, exactly like the single-device step).
    Returns updated locals + the post-push mid-step snapshot (pos, u right
    before migration — the windowed driver carries it so a discarded
    recv-drop step replays only migration onward) + stats dict.

    ``use_mid`` (traced bool scalar, windowed replay only): substitute the
    carried ``mid_pos``/``mid_u`` for this step's own push output. Weights
    and alive masks are untouched by the push, so the migration inputs of
    the replay match the discarded step's bit for bit. ``None`` omits the
    substitution from the program entirely."""
    ex, ey, ez, bx, by, bz = fields
    g = cfg.guard
    shape = cfg.local_grid.shape
    layout = BinnedLayout(slots=slots, particle_slot=particle_slot)

    # unmigrated send-overflow particles from the previous step: alive but
    # out-of-range, NOT in any bin (gather returns 0 for them), frozen for
    # this step — migration below retries them
    resident = alive & in_domain(pos, shape)

    # 1. halo-extended fields + gather
    with jax.named_scope("pic.halo"):
        pe = [_extend_all(f, g, cfg) for f in (ex, ey, ez)]
        pb = [_extend_all(f, g, cfg) for f in (bx, by, bz)]
    with jax.named_scope("pic.gather"):
        if cfg.gather == "matrix":
            # fused six-component pass over the carried slab (one staging, six
            # shared weight sets, one slot-map scatter-back); the contraction
            # backend resolves through the kernel dispatcher
            e_p, b_p = gather_fields_fused(
                BinSlab(d=slab_d, valid=slab_valid), tuple(pe) + tuple(pb), layout,
                grid_shape=shape, order=cfg.order, backend=cfg.backend,
            )
        else:  # matrix_unfused: six-call comparison mode
            e_p = jnp.stack(
                [gather_matrix(pos, pe[k], layout, grid_shape=shape, order=cfg.order, stagger=E_STAGGER[k], backend=cfg.backend) for k in range(3)], -1
            )
            b_p = jnp.stack(
                [gather_matrix(pos, pb[k], layout, grid_shape=shape, order=cfg.order, stagger=B_STAGGER[k], backend=cfg.backend) for k in range(3)], -1
            )

    # 2. push (positions NOT wrapped: out-of-range triggers migration);
    # frozen out-of-domain particles keep position AND momentum so they
    # retry migration with the same coordinates
    with jax.named_scope("pic.push"):
        u_new = jnp.where(resident[:, None], boris_push(u, e_p, b_p, cfg.charge / cfg.mass, cfg.dt), u)
        pos_new = jnp.where(resident[:, None], advance_positions(pos, u_new, cfg.dt, cfg.local_grid.dx), pos)

        # 3. migration (x then y; z wraps locally)
        pos_new = pos_new.at[:, 2].set(jnp.mod(pos_new[:, 2], shape[2]))
        if use_mid is not None:
            pos_new = jnp.where(use_mid, mid_pos, pos_new)
            u_new = jnp.where(use_mid, mid_u, u_new)
    # post-push / pre-migration snapshot (returned for the window carry)
    mid_pos_out, mid_u_out = pos_new, u_new
    mig_send_overflow = jnp.int32(0)
    mig_recv_dropped = jnp.int32(0)
    arrived = jnp.zeros_like(alive)
    compress = cfg.comm.compress_migration
    with jax.named_scope("pic.migrate"):
        for ax_name in cfg.x_axes:
            pos_new, u_new, w, alive, of, dr, ins = migrate_axis(
                pos_new, u_new, w, alive, coord=0, extent=shape[0], axis_name=ax_name, mig_cap=cfg.mig_cap,
                local_shape=shape, compress=compress,
            )
            mig_send_overflow += of
            mig_recv_dropped += dr
            arrived |= ins
        for ax_name in cfg.y_axes:
            pos_new, u_new, w, alive, of, dr, ins = migrate_axis(
                pos_new, u_new, w, alive, coord=1, extent=shape[1], axis_name=ax_name, mig_cap=cfg.mig_cap,
                local_shape=shape, compress=compress,
            )
            mig_send_overflow += of
            mig_recv_dropped += dr
            arrived |= ins

    # 4. incremental sort on local bins — send-overflow stragglers are kept
    # OUT of the bins (they retry migration next step; binning them would
    # clip their cell index into the boundary cell and corrupt the gather
    # and deposition with out-of-range shape weights)
    with jax.named_scope("pic.gpma"):
        binned = alive & in_domain(pos_new, shape)
        new_cells = cell_index(pos_new, shape)
        # churn accounting for migrated-in arrivals: gpma_update counts an
        # arrival as a move when its (stale or invalid) particle_slot maps a
        # DIFFERENT cell, but an arrival that reuses a just-departed index whose
        # stale slot happens to sit in the arrival's own cell looks stationary
        # to it. A boundary crossing is one move no matter which shard observes
        # it (the departure side frees the particle as dead, contributing
        # nothing), so add those invisible arrivals back — keeping the
        # moved-fraction perf proxy's churn identical to single-device.
        stale_cell = jnp.where(particle_slot >= 0, particle_slot // cfg.capacity, -1)
        n_arrived_invisible = jnp.sum(arrived & binned & (new_cells == stale_cell))
        layout, gstats = gpma_update(layout, new_cells, binned)
        # ...and arrivals whose first insert hit a FULL bin: gpma only counts a
        # fresh unslotted insert when it lands, but the crossing happened this
        # step regardless — count it now. The particle is not recounted while
        # it WAITS; the eventual landing does count once more (the same bounded
        # stall-then-land overcount gpma_update documents), but on this driver
        # the nonzero overflow mandatory-sorts the very same step, so stalled
        # arrivals never persist into a later gpma landing in practice.
        n_arrived_invisible = n_arrived_invisible + jnp.sum(
            arrived & binned & (stale_cell < 0) & (layout.particle_slot < 0)
        )

    # 5-prep: push-derived deposition inputs, computed BEFORE the staging
    # so the fused matrix path can stage positions and q·w·v values through
    # one slot-table gather (binned particles only: the layout already
    # excludes stragglers, qw masking keeps the oracle identical)
    with jax.named_scope("pic.push"):
        gamma = lorentz_gamma(u_new)
        v = u_new / gamma[:, None]
        qw = cfg.charge * w * binned.astype(w.dtype)

    # 4b. the step's ONE slab staging, consistent with (pos_new, layout):
    # consumed by the fused deposition below and carried for the next
    # step's fused gather (pure-unfused ablation configs carry the input
    # slab through untouched — nothing consumes it). The matrix deposition
    # stages its value slab through the same gather.
    with jax.named_scope("pic.stage"):
        values = None
        if cfg.deposition == "matrix":
            slab, values = bin_slab_staging(pos_new, v, qw, layout, grid_shape=shape)
        elif cfg.needs_slab:
            slab = build_bin_slab(pos_new, layout, grid_shape=shape)
        else:
            slab = BinSlab(d=slab_d, valid=slab_valid)

    # 5. deposition + guard reduction (the reduction is the halo exchange)
    inv_vol = 1.0 / cfg.local_grid.cell_volume
    if cfg.deposition == "matrix":
        with jax.named_scope("pic.deposit"):
            j3 = deposit_current_matrix_fused(
                pos_new, v, qw, layout, grid_shape=shape, order=cfg.order,
                backend=cfg.backend, slab=slab, values=values,
            )
        with jax.named_scope("pic.halo"):
            j = [_reduce_all(jp, g, cfg) * inv_vol for jp in j3]
    else:  # matrix_unfused: per-component comparison mode
        j = []
        for k, stagger in enumerate(((True, False, False), (False, True, False), (False, False, True))):
            with jax.named_scope("pic.deposit"):
                jp = deposit_matrix(
                    pos_new, qw * v[:, k], layout, grid_shape=shape, order=cfg.order, stagger=stagger,
                    backend=cfg.backend,
                )
            with jax.named_scope("pic.halo"):
                j.append(_reduce_all(jp, g, cfg) * inv_vol)

    # 6. Maxwell (1-cell halos, slice curls), B-E-B leapfrog; its halo
    # extensions run under pic.halo inside pic.maxwell
    def half_b(exc, eyc, ezc, bxc, byc, bzc, dt_half):
        with jax.named_scope("pic.halo"):
            epad = [_extend_all(f, 1, cfg) for f in (exc, eyc, ezc)]
        cx, cy, cz = curl_e_padded(*epad, 1, shape, cfg.local_grid.dx)
        return bxc - dt_half * cx, byc - dt_half * cy, bzc - dt_half * cz

    with jax.named_scope("pic.maxwell"):
        bx1, by1, bz1 = half_b(ex, ey, ez, bx, by, bz, 0.5 * cfg.dt)
        with jax.named_scope("pic.halo"):
            bpad = [_extend_all(f, 1, cfg) for f in (bx1, by1, bz1)]
        cx, cy, cz = curl_b_padded(*bpad, 1, shape, cfg.local_grid.dx)
        ex1 = ex + cfg.dt * (cx - j[0])
        ey1 = ey + cfg.dt * (cy - j[1])
        ez1 = ez + cfg.dt * (cz - j[2])
        bx2, by2, bz2 = half_b(ex1, ey1, ez1, bx1, by1, bz1, 0.5 * cfg.dt)

    # per-step communication accounting (comm co-design observability):
    # the migration payload is statically sized — every migrate_axis call
    # ships 2 directions × mig_cap rows regardless of occupancy — so the
    # per-shard wire bytes are a config constant; psum turns them into the
    # global per-step traffic the BENCH_comm rows report.
    row_bytes = MIG_ROW_BYTES_COMPRESSED if cfg.comm.compress_migration else MIG_ROW_BYTES_EXACT
    n_axis_calls = len(cfg.x_axes) + len(cfg.y_axes)
    stats = {
        "n_moved": gstats.n_moved + n_arrived_invisible,
        "n_overflow": gstats.n_overflow,
        "n_empty": gstats.n_empty,
        "mig_send_overflow": mig_send_overflow,
        "mig_recv_dropped": mig_recv_dropped,
        "n_unmigrated": jnp.sum(alive & ~in_domain(pos_new, shape)).astype(jnp.int32),
        "n_alive": jnp.sum(alive),
        "n_migrated": jnp.sum(arrived).astype(jnp.int32),
        "mig_payload_bytes": jnp.int32(2 * cfg.mig_cap * row_bytes * n_axis_calls),
        "n_ranked": gstats.n_ranked,
    }
    # global sums for the resort policy (host- or in-graph)
    for k in list(stats):
        stats[k] = psum_all(stats[k], cfg)
    # peak per-shard occupancy: the load-imbalance signal behind
    # HALT_IMBALANCE (pmax, not psum — n_alive above is the global total)
    stats["max_shard_alive"] = pmax_all(jnp.sum(alive), cfg)

    return (ex1, ey1, ez1, bx2, by2, bz2), pos_new, u_new, w, alive, layout.slots, layout.particle_slot, slab.d, slab.valid, mid_pos_out, mid_u_out, stats


def psum_all(value, cfg: DistConfig):
    """Sum a per-shard scalar over every decomposed mesh axis."""
    for ax in cfg.x_axes + cfg.y_axes:
        value = lax.psum(value, ax)
    return value


def pmax_all(value, cfg: DistConfig):
    """Max of a per-shard scalar over every decomposed mesh axis."""
    for ax in cfg.x_axes + cfg.y_axes:
        value = lax.pmax(value, ax)
    return value


STAT_KEYS = (
    "n_moved", "n_overflow", "n_empty", "mig_send_overflow",
    "mig_recv_dropped", "n_unmigrated", "n_alive",
    "n_migrated", "mig_payload_bytes", "max_shard_alive", "n_ranked",
)


def dist_global_sort_device(pos, u, w, alive, cfg: DistConfig):
    """Per-shard GlobalSortParticlesByCell, traceable (runs under `lax.cond`
    inside the windowed shard_map driver): permute the shard's attribute
    arrays into cell order + rebuild the local bins AND the staging slab
    (the permutation invalidates both), returning the LOCAL overflow as a
    traced int32 (callers psum it).

    Unmigrated send-overflow stragglers (alive, out-of-domain) sort to the
    back with the dead particles and stay out of the bins, but keep their
    alive flag — they retry migration on the next step.
    """
    shape = cfg.local_grid.shape
    with jax.named_scope("pic.global_sort"):
        binned = alive & in_domain(pos, shape)
        perm = sort_permutation(cell_index(pos, shape), binned)
        pos, u, w, alive = pos[perm], u[perm], w[perm], alive[perm]
        binned = alive & in_domain(pos, shape)
        layout, overflow = build_bins(
            cell_index(pos, shape), binned, n_cells=cfg.local_grid.n_cells, capacity=cfg.capacity
        )
        slab = build_bin_slab(pos, layout, grid_shape=shape)
        return pos, u, w, alive, layout.slots, layout.particle_slot, slab.d, slab.valid, overflow.astype(jnp.int32)


def make_dist_step(mesh, cfg: DistConfig):
    """Build the jitted shard_map step. Array layout (host view):
      fields: (NX, NY, NZ) sharded P(x_axes, y_axes, None)
      particles: (SX, SY, Nloc, ...) sharded on the two leading axes.
    """
    validate_shard_guard(cfg.local_grid, cfg.order)
    cfg = resolve_sharded_backend(cfg)
    fspec = P(cfg.x_axes, cfg.y_axes, None)

    def spec(*extra):
        return P(cfg.x_axes, cfg.y_axes, *extra)

    in_specs = (
        (fspec,) * 6,
        spec(None, None),        # pos (SX,SY,Nloc,3)
        spec(None, None),        # u
        spec(None),              # w
        spec(None),              # alive
        spec(None, None),        # slots
        spec(None),              # particle_slot
        spec(None, None, None),  # slab_d (SX,SY,C,cap,3)
        spec(None, None),        # slab_valid (SX,SY,C,cap)
    )
    out_specs = (
        (fspec,) * 6,
        spec(None, None), spec(None, None), spec(None), spec(None),
        spec(None, None), spec(None),
        spec(None, None, None), spec(None, None),
        {k: P() for k in STAT_KEYS},
    )

    def body(fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid):
        # strip the (1,1) leading shard dims from particle arrays
        sq = lambda a: a.reshape(a.shape[2:])
        fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid, _mid_pos, _mid_u, stats = dist_pic_step_local(
            fields, sq(pos), sq(u), sq(w), sq(alive), sq(slots), sq(pslot),
            sq(slab_d), sq(slab_valid), cfg
        )
        ex = lambda a: a.reshape((1, 1) + a.shape)
        return (fields, ex(pos), ex(u), ex(w), ex(alive), ex(slots), ex(pslot),
                ex(slab_d), ex(slab_valid), stats)

    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return jax.jit(sm)


def make_dist_sort(mesh, cfg: DistConfig):
    """Jitted shard_map per-shard global sort (attribute permutation + bin
    AND slab rebuild at ``cfg.capacity``). Host escape hatch used by the
    per-step host loop; the windowed driver grows capacity through the
    halt-and-grow protocol instead (pad + in-graph presort — see
    DistSimulation._grow_capacity). Returns
    ``(pos, u, w, alive, slots, pslot, slab_d, slab_valid, overflow)`` with
    overflow psum-reduced (replicated scalar)."""

    def spec(*extra):
        return P(cfg.x_axes, cfg.y_axes, *extra)

    part_specs = (spec(None, None), spec(None, None), spec(None), spec(None))
    in_specs = part_specs
    out_specs = (*part_specs, spec(None, None), spec(None),
                 spec(None, None, None), spec(None, None), P())

    def body(pos, u, w, alive):
        sq = lambda a: a.reshape(a.shape[2:])
        pos, u, w, alive, slots, pslot, slab_d, slab_valid, overflow = dist_global_sort_device(
            sq(pos), sq(u), sq(w), sq(alive), cfg
        )
        ex = lambda a: a.reshape((1, 1) + a.shape)
        return (ex(pos), ex(u), ex(w), ex(alive), ex(slots), ex(pslot),
                ex(slab_d), ex(slab_valid), psum_all(overflow, cfg))

    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    return jax.jit(sm)


# ---------------------------------------------------------------------------
# host-side partitioning helpers
# ---------------------------------------------------------------------------

def partition_particles(parts: ParticleState, global_grid: GridSpec, sx: int, sy: int, n_local: int):
    """Split a global ParticleState into (SX, SY, Nloc) local arrays with
    local-frame positions. Fails loudly if any shard exceeds n_local."""
    import numpy as np

    nx_loc = global_grid.shape[0] // sx
    ny_loc = global_grid.shape[1] // sy
    pos = np.asarray(parts.pos)
    u = np.asarray(parts.u)
    w = np.asarray(parts.w)
    alive = np.asarray(parts.alive)

    out_pos = np.zeros((sx, sy, n_local, 3), np.float32)
    out_u = np.zeros((sx, sy, n_local, 3), np.float32)
    out_w = np.zeros((sx, sy, n_local), np.float32)
    out_alive = np.zeros((sx, sy, n_local), bool)

    ix = np.clip((pos[:, 0] // nx_loc).astype(int), 0, sx - 1)
    iy = np.clip((pos[:, 1] // ny_loc).astype(int), 0, sy - 1)
    for a in range(sx):
        for b in range(sy):
            m = alive & (ix == a) & (iy == b)
            k = int(m.sum())
            assert k <= n_local, f"shard ({a},{b}) holds {k} > n_local={n_local}"
            local = pos[m].copy()
            local[:, 0] -= a * nx_loc
            local[:, 1] -= b * ny_loc
            out_pos[a, b, :k] = local
            out_u[a, b, :k] = u[m]
            out_w[a, b, :k] = w[m]
            out_alive[a, b, :k] = True
    return (jnp.asarray(out_pos), jnp.asarray(out_u), jnp.asarray(out_w), jnp.asarray(out_alive))


def build_local_bins(pos, alive, local_grid: GridSpec, capacity: int):
    """Vectorized over the two leading shard dims (host-side init). Returns
    the per-shard bins AND the initial `BinSlab` staging arrays (the first
    step's gather consumes the slab, like the single-device init)."""
    sx, sy = pos.shape[:2]
    f = lambda p, a: build_bins(cell_index(p, local_grid.shape), a, n_cells=local_grid.n_cells, capacity=capacity)
    slots, pslot, slab_d, slab_valid, overflow = [], [], [], [], 0
    for a in range(sx):
        srow, prow, drow, vrow = [], [], [], []
        for b in range(sy):
            layout, of = f(pos[a, b], alive[a, b])
            slab = build_bin_slab(pos[a, b], layout, grid_shape=local_grid.shape)
            srow.append(layout.slots)
            prow.append(layout.particle_slot)
            drow.append(slab.d)
            vrow.append(slab.valid)
            overflow += int(of)
        slots.append(jnp.stack(srow))
        pslot.append(jnp.stack(prow))
        slab_d.append(jnp.stack(drow))
        slab_valid.append(jnp.stack(vrow))
    return jnp.stack(slots), jnp.stack(pslot), jnp.stack(slab_d), jnp.stack(slab_valid), overflow
