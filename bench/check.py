"""The comparison that decides ``correct``: the program's state after the
checked window against the plain reference's, from the same start.

Every number is a gap that a sound run keeps near rounding and a wrong
one does not, and none depends on the particles' order (the program's
sort permutes them):

* ``field_gap``: the largest difference of any E or B node between the
  program and the reference, over the largest change the plasma made to
  the fields in the reference (reference minus its vacuum evolution). It
  covers gather, push, binning and sort, deposition and the field solve.
* ``momentum_gap``: the largest difference between the sorted momenta of
  the two, per component, over the largest reference momentum.
* ``charge_gap``: the difference in total charge-weight of the live
  particles plus the difference in their number. Exact: the step neither
  makes nor loses particles.
* ``position_gap``: the largest difference between the sorted positions
  of the two, per axis, in cells. A cell's limits file decides which of
  these numbers are compared; this one is printed beside them.
"""

from __future__ import annotations

import math

import numpy as np


def field_gap(got_fields, ref_fields, vac_fields) -> float:
    diff = max(float(np.max(np.abs(np.asarray(g, np.float64) - np.asarray(r, np.float64))))
               for g, r in zip(got_fields, ref_fields))
    response = max(float(np.max(np.abs(np.asarray(r, np.float64) - np.asarray(v, np.float64))))
                   for r, v in zip(ref_fields, vac_fields))
    return diff / response if response > 0 else math.inf


def momentum_gap(got_u, ref_u) -> float:
    got_u, ref_u = np.asarray(got_u, np.float64), np.asarray(ref_u, np.float64)
    scale = float(np.max(np.abs(ref_u)))
    diff = max(float(np.max(np.abs(np.sort(got_u[:, a]) - np.sort(ref_u[:, a])))) for a in range(3))
    return diff / scale if scale > 0 else math.inf


def position_gap(got_pos, ref_pos, box) -> float:
    """A particle that crossed the periodic boundary on one side only moves
    one place in the sorted order; shifts of up to two places, with the
    wrapped ends moved by a box length, are tried and the least gap taken."""
    worst = 0.0
    for a, length in enumerate(box):
        g = np.sort(np.asarray(got_pos, np.float64)[:, a])
        r = np.sort(np.asarray(ref_pos, np.float64)[:, a])
        best = math.inf
        for k in range(-2, 3):
            shifted = np.roll(g, -k)
            if k > 0:
                shifted[-k:] += length
            elif k < 0:
                shifted[:-k] -= length
            best = min(best, float(np.max(np.abs(shifted - r))))
        worst = max(worst, best)
    return worst


def charge_gap(got: dict, ref: dict) -> float:
    def charge(s):
        alive = np.asarray(s["alive"], bool)
        return math.fsum(np.asarray(s["w"], np.float64)[alive].tolist()), int(alive.sum())

    (q_got, n_got), (q_ref, n_ref) = charge(got), charge(ref)
    return abs(q_got - q_ref) + abs(n_got - n_ref)


def compare(got: dict, ref: dict) -> dict[str, float]:
    """The numbers compared, by name. ``got`` is the program's state after
    the checked window; ``ref`` is `reference.run`'s output."""
    return {
        "field_gap": field_gap(got["fields"], ref["fields"], ref["vacuum"]),
        "momentum_gap": momentum_gap(got["u"], ref["u"]),
        "charge_gap": charge_gap(got, ref),
        "position_gap": position_gap(got["pos"], ref["pos"], np.shape(ref["fields"][0])),
    }


def within(checks: dict) -> bool:
    """Every number at or below its limit (a NaN is never within)."""
    return all(c["value"] <= c["limit"] for c in checks.values())
