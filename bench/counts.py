"""The work the algorithm needs, counted from shapes: FLOPs and bytes of
one step's field gather and current deposition, and the roofline share
they give. These are minimums of the algorithm, not of any route that
implements it, so the same work reads the same whether XLA or a Pallas
kernel does it.

The canonical FLOPs per particle are the paper's scalar deposition work
(three current components; 61, 190, 419 for orders 1-3), kept here as
a copy so that no change to the program moves the yardstick.
"""

from __future__ import annotations

import json
from pathlib import Path

CANONICAL_DEPOSITION_FLOPS = {1: 61, 2: 190, 3: 419}
F32 = 4
#: position (3), velocity (3) and charge-weight (1), float32
DEPOSITION_BYTES_PER_PARTICLE = 7 * F32
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def deposition_work(order: int, n_particles: int, n_cells: int) -> tuple[float, float]:
    """(FLOPs, bytes): the canonical work per weighted particle; each
    particle's inputs read once and three current grids written once."""
    flops = CANONICAL_DEPOSITION_FLOPS[order] * n_particles
    return float(flops), float(n_particles * DEPOSITION_BYTES_PER_PARTICLE + 3 * n_cells * F32)


def gather_work(order: int, n_particles: int, n_cells: int) -> tuple[float, float]:
    """(FLOPs, bytes): six components, (order+1)^3 taps, a multiply and an
    add per tap; six field grids and the positions read once, six values
    per particle written once."""
    flops = 6 * (order + 1) ** 3 * 2 * n_particles
    return float(flops), float(6 * n_cells * F32 + n_particles * 3 * F32 + n_particles * 6 * F32)


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``. A device that is not in the
    table is an error: there is no default peak."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table has {sorted(table)}")
    return table[device_kind]


def roofline_share(flops: float, nbytes: float, seconds: float, peak: dict) -> tuple[float, str]:
    """(percent of the roofline, which bound): the least time the chip
    could take, the larger of FLOPs over peak FLOP/s and bytes over peak
    bandwidth, over the time measured."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
