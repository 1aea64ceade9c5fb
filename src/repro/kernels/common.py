"""Shared plumbing for the Pallas kernel packages.

Two concerns every kernel family (deposition, gather, scatter_matrix) was
solving with copy-pasted code:

  * interpret-mode detection — the kernels are written for the TPU Mosaic
    compiler; on any other backend (CPU CI, GPU dev boxes) they must run
    under the Pallas interpreter, which executes the kernel body as written.
  * block sizing — the grid tiles the leading (cell/bin) axis so each grid
    step's working set fits VMEM. The autotuner picks the largest block
    that fits a VMEM budget, rounded down to a sublane-friendly multiple.

Callers describe their per-cell working set in bytes (`vmem_bytes` of the
per-cell block shapes: inputs + operands built in-kernel + output tile)
and get a block size back; `interpret=None` anywhere in the kernel APIs
means "auto-detect".
"""

from __future__ import annotations

import math

import jax

#: Per-grid-step budget the block autotuner fills on hardware: the
#: tile-padded (see `vmem_bytes`) working set of one block, with its input
#: and output blocks counted twice for Mosaic's double-buffered pipeline.
#: A TPU v5e core has 128 MiB of VMEM, but Mosaic's default scoped limit
#: is 16 MiB; half of that leaves the compiler room for temporaries the
#: byte model does not see (relayouts, spills). The kernels request no
#: larger limit: on a v5e, `bin_gather` compiled with a 64 MiB request
#: never returned, and returned in about 1 ms without it.
DEFAULT_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: Budget under the interpreter, where there is no physical VMEM and
#: per-grid-step overhead dominates (calibrated on unpadded bytes).
INTERPRET_VMEM_BUDGET_BYTES = 4 * 1024 * 1024

#: Sublane-friendly rounding for the blocked (cell/bin) axis.
BLOCK_MULTIPLE = 8

#: Under the interpreter the autotuner widens its budget by this factor
#: (fewer, larger blocks; the TPU-shaped budget still governs on hardware).
INTERPRET_BUDGET_SCALE = 16

#: Mosaic lays the two minor dims of every VMEM array out in (8, 128)
#: f32 tiles: a (cap, 3) slab occupies (roundup(cap, 8), 128) words.
SUBLANES, LANES = 8, 128


def autodetect_interpret() -> bool:
    """True when the Mosaic TPU compiler is unavailable for pallas_call."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """None means auto-detect; an explicit bool is respected as-is."""
    return autodetect_interpret() if interpret is None else bool(interpret)


def vmem_bytes(*shapes: tuple[int, ...], tiled: bool, itemsize: int = 4) -> int:
    """Bytes the arrays of ``shapes`` occupy in VMEM.

    ``tiled=True`` counts Mosaic's (8, 128) tiling of the two minor dims —
    a (32, 3) f32 slab takes 16 KiB, not 384 B. ``tiled=False`` counts the
    raw bytes (the interpreter's view)."""
    total = 0
    for shape in shapes:
        shape = tuple(int(s) for s in shape) or (1,)
        if tiled:
            shape = (1,) * max(0, 2 - len(shape)) + shape
            lead = math.prod(shape[:-2])
            sub = -(-shape[-2] // SUBLANES) * SUBLANES
            lane = -(-shape[-1] // LANES) * LANES
            total += lead * sub * lane * itemsize
        else:
            total += math.prod(shape) * itemsize
    return total


#: Tap-window width the interpret budget is calibrated against (order 1's
#: unified window). Wider windows scale the budget quadratically — see
#: choose_block_cells.
INTERPRET_REFERENCE_TAPS = 3


def choose_block_cells(
    n_cells: int,
    per_cell_bytes: int,
    *,
    vmem_budget_bytes: int | None = None,
    multiple: int = BLOCK_MULTIPLE,
    interpret: bool = False,
    taps: int | None = None,
) -> int:
    """Largest leading-axis block whose working set fits the VMEM budget.

    Args:
      n_cells: extent of the blocked axis (upper bound for the block).
      per_cell_bytes: bytes of VMEM one cell/bin of the block consumes —
        count kernel inputs, in-kernel intermediates, and the output tile
        (`vmem_bytes`, tiled when compiled).
      vmem_budget_bytes: per-grid-step budget; None picks
        DEFAULT_VMEM_BUDGET_BYTES compiled, INTERPRET_VMEM_BUDGET_BYTES
        (then widened, see ``interpret``) under the interpreter.
      multiple: round blocks >= this down to a multiple of it (sublane
        alignment); smaller blocks are kept exact so tiny problems still run.
      interpret: widen the budget by INTERPRET_BUDGET_SCALE (no physical
        VMEM under the interpreter; per-step overhead dominates instead).
      taps: the kernel's unified tap-window width, when it has one. Under
        the interpreter a single byte budget penalizes wide-tap orders:
        their per-cell working set grows ~taps^2 (the packed rhocell tile
        dominates), so a fixed budget splits an order-3 problem into extra
        grid steps long before an order-1 problem of the same byte size —
        and per-grid-step overhead, not locality, is what the interpreter
        pays for. Scaling the widened budget by
        (taps / INTERPRET_REFERENCE_TAPS)^2 keeps the *cell count* at
        which a problem first splits roughly order-independent.
    """
    if vmem_budget_bytes is None:
        vmem_budget_bytes = INTERPRET_VMEM_BUDGET_BYTES if interpret else DEFAULT_VMEM_BUDGET_BYTES
    if interpret:
        scale = INTERPRET_BUDGET_SCALE
        if taps is not None and taps > INTERPRET_REFERENCE_TAPS:
            scale = (scale * taps * taps) // (INTERPRET_REFERENCE_TAPS**2)
        vmem_budget_bytes *= scale
    block = max(1, min(int(n_cells), vmem_budget_bytes // max(int(per_cell_bytes), 1)))
    if block >= multiple:
        block -= block % multiple
    if block < n_cells:
        # balance the grid: the same number of steps with even blocks beats
        # a ragged tiny tail block (each step pays fixed overhead)
        steps = -(-int(n_cells) // block)
        even = -(-int(n_cells) // steps)
        if even >= multiple:
            even += (-even) % multiple
        if even <= block:
            block = even
    return block
