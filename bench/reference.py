"""Plain particle-in-cell reference: the physics of one step, written out.

Independent of the program under test (it imports nothing from ``src``):
B-spline shapes of order 1-3, the relativistic Boris push, the explicit
position update on a periodic box, direct scatter-add current deposition
on the Yee stagger, and the leapfrog Maxwell update (half B, full E, half
B). Every operation is elementwise arithmetic, a row gather or a row
scatter-add: nothing goes through the matrix unit, so float32 here is
float32 on any backend. A particle reads and writes one row of its cell:
the W^3 nodes around the cell (W = 2 ceil((order+1)/2) + 1), whose
weights outside the particle's support are exactly zero; the rows come
from, and go back to, the grid by periodic shifts. Particles are processed
in chunks.

Conventions (normalized units, c = eps0 = mu0 = 1): positions in cell
units on a periodic box; the node of a component that is staggered along
an axis sits at ``i + 1/2`` on that axis. E and J: x-, y-, z-staggered
along their own axis; B: staggered along the two others.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

E_STAGGER = ((1, 0, 0), (0, 1, 0), (0, 0, 1))
B_STAGGER = ((0, 1, 1), (1, 0, 1), (1, 1, 0))
CHUNK = 1 << 17


def bspline(order: int, u):
    """Centered cardinal B-spline of the given order at distance ``u``."""
    a = jnp.abs(u)
    zero = jnp.zeros_like(a)
    if order == 1:
        return jnp.maximum(zero, 1.0 - a)
    if order == 2:
        return jnp.where(a < 0.5, 0.75 - a * a, jnp.where(a < 1.5, 0.5 * (1.5 - a) ** 2, zero))
    if order == 3:
        return jnp.where(
            a < 1.0, 2.0 / 3.0 - a * a + 0.5 * a * a * a,
            jnp.where(a < 2.0, (2.0 - a) ** 3 / 6.0, zero),
        )
    raise ValueError(f"shape order must be 1, 2 or 3, got {order}")


def _half(order: int) -> int:
    """Half-width of the support in nodes: a node within ``(order+1)/2`` of
    a particle lies at most this many cells from the particle's cell."""
    return math.ceil((order + 1) / 2)


def _cell_weights(pos, order: int, stagger):
    """Each particle's cell (flat index) and the weights of the
    ``W^3`` nodes around it, ``W = 2 half + 1``: node ``c + k`` for
    ``k = -half .. half`` on each axis, weight the product of the 1-D
    B-spline factors (zero outside the support)."""
    h = _half(order)
    cell = jnp.floor(pos).astype(jnp.int32)
    k = jnp.arange(-h, h + 1, dtype=jnp.int32)
    w = []
    for a in range(3):
        node = (cell[:, a:a + 1] + k[None, :]).astype(pos.dtype) + 0.5 * stagger[a]
        w.append(bspline(order, pos[:, a:a + 1] - node))
    w3 = w[0][:, :, None, None] * w[1][:, None, :, None] * w[2][:, None, None, :]
    return cell, w3.reshape(pos.shape[0], -1)


def _flat_cell(cell, shape):
    c = jnp.mod(cell, jnp.asarray(shape, jnp.int32))
    return (c[:, 0] * shape[1] + c[:, 1]) * shape[2] + c[:, 2]


def _offsets(order: int):
    h = _half(order)
    r = range(-h, h + 1)
    return [(a, b, c) for a in r for b in r for c in r]


def _neighbourhoods(f, order: int):
    """(cells, W^3): row ``i`` holds the field at the W^3 nodes around cell
    ``i``, in the order of `_offsets` (periodic)."""
    cols = [jnp.roll(f, (-a, -b, -c), axis=(0, 1, 2)).reshape(-1) for a, b, c in _offsets(order)]
    return jnp.stack(cols, axis=1)


def _spread(rows, order: int, shape):
    """The transpose of `_neighbourhoods`: add each cell's row back onto the
    nodes around the cell."""
    out = jnp.zeros(shape, rows.dtype)
    for k, (a, b, c) in enumerate(_offsets(order)):
        out = out + jnp.roll(rows[:, k].reshape(shape), (a, b, c), axis=(0, 1, 2))
    return out


def _chunked(n: int):
    size = min(n, CHUNK)
    return size, -(-n // size)


def _pad_rows(a, rows: int):
    return jnp.concatenate([a, jnp.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)])


def gather(fields, pos, order: int):
    """(N, 3) E and (N, 3) B at the particles: the node values around each
    particle's cell, weighted by its shape factors and summed."""
    shape = fields[0].shape
    n = pos.shape[0]
    size, n_chunks = _chunked(n)
    pos_c = _pad_rows(pos, size * n_chunks).reshape(n_chunks, size, 3)
    rows = [_neighbourhoods(f, order) for f in fields]

    def one(p):
        out = []
        for nb, st in zip(rows, E_STAGGER + B_STAGGER):
            cell, w = _cell_weights(p, order, st)
            out.append(jnp.sum(nb[_flat_cell(cell, shape)] * w, axis=1))
        return jnp.stack(out, axis=-1)

    eb = jax.lax.map(one, pos_c).reshape(n_chunks * size, 6)[:n]
    return eb[:, :3], eb[:, 3:]


def deposit(pos, values, order: int, shape):
    """Current grids [Jx, Jy, Jz] (charge per cell volume of 1): each
    particle adds value times its shape factors to a row of its cell
    (the nodes around the cell), and the rows are spread onto the grid."""
    n = pos.shape[0]
    size, n_chunks = _chunked(n)
    pos_c = _pad_rows(pos, size * n_chunks).reshape(n_chunks, size, 3)
    val_c = _pad_rows(values, size * n_chunks).reshape(n_chunks, size, 3)
    n_cells = shape[0] * shape[1] * shape[2]
    width = (2 * _half(order) + 1) ** 3

    def body(acc, chunk):
        p, v = chunk
        comps = []
        for k, st in enumerate(E_STAGGER):
            cell, w = _cell_weights(p, order, st)
            comps.append(acc[k].at[_flat_cell(cell, shape)].add(w * v[:, k:k + 1]))
        return jnp.stack(comps), None

    rho, _ = jax.lax.scan(body, jnp.zeros((3, n_cells, width), values.dtype), (pos_c, val_c))
    return tuple(_spread(r, order, shape) for r in rho)


def gamma(u):
    return jnp.sqrt(1.0 + jnp.sum(u * u, axis=-1))


def boris(u, e, b, q_over_m: float, dt: float):
    """Relativistic Boris rotation: half electric kick, magnetic rotation,
    half electric kick."""
    h = 0.5 * dt * q_over_m
    u_minus = u + h * e
    t = h * b / gamma(u_minus)[:, None]
    s = 2.0 * t / (1.0 + jnp.sum(t * t, axis=-1, keepdims=True))
    u_prime = u_minus + jnp.cross(u_minus, t)
    return u_minus + jnp.cross(u_prime, s) + h * e


def _diff_up(f, axis, d):
    return (jnp.roll(f, -1, axis=axis) - f) / d


def _diff_down(f, axis, d):
    return (f - jnp.roll(f, 1, axis=axis)) / d


def maxwell(fields, j, dt: float, dx):
    """Yee leapfrog on the periodic box: B half step, E full step with
    the current, B half step."""
    ex, ey, ez, bx, by, bz = fields

    def half_b(ex, ey, ez, bx, by, bz):
        h = 0.5 * dt
        return (
            bx - h * (_diff_up(ez, 1, dx[1]) - _diff_up(ey, 2, dx[2])),
            by - h * (_diff_up(ex, 2, dx[2]) - _diff_up(ez, 0, dx[0])),
            bz - h * (_diff_up(ey, 0, dx[0]) - _diff_up(ex, 1, dx[1])),
        )

    bx, by, bz = half_b(ex, ey, ez, bx, by, bz)
    jx, jy, jz = j
    ex = ex + dt * (_diff_down(bz, 1, dx[1]) - _diff_down(by, 2, dx[2]) - jx)
    ey = ey + dt * (_diff_down(bx, 2, dx[2]) - _diff_down(bz, 0, dx[0]) - jy)
    ez = ez + dt * (_diff_down(by, 0, dx[0]) - _diff_down(bx, 1, dx[1]) - jz)
    bx, by, bz = half_b(ex, ey, ez, bx, by, bz)
    return (ex, ey, ez, bx, by, bz)


@partial(jax.jit, static_argnames=("order", "dt", "charge", "mass", "dx"))
def step(fields, pos, u, w, alive, *, order: int, dt: float, charge: float, mass: float, dx):
    """One PIC step: gather at x^n, push u to n+1/2, move to x^{n+1},
    deposit q w v^{n+1/2} at x^{n+1}, advance the fields. Particles that
    are not alive neither move nor deposit."""
    shape = fields[0].shape
    e, b = gather(fields, pos, order)
    u_new = jnp.where(alive[:, None], boris(u, e, b, charge / mass, dt), u)
    v = u_new / gamma(u_new)[:, None]
    inv_dx = jnp.asarray([1.0 / d for d in dx], pos.dtype)
    pos_new = jnp.mod(pos + dt * v * inv_dx, jnp.asarray(shape, pos.dtype))
    pos_new = jnp.where(alive[:, None], pos_new, pos)
    qw = charge * w * alive.astype(w.dtype)
    j = deposit(pos_new, qw[:, None] * v, order, shape)
    inv_vol = 1.0 / (dx[0] * dx[1] * dx[2])
    fields = maxwell(fields, tuple(c * inv_vol for c in j), dt, dx)
    return fields, pos_new, u_new


@partial(jax.jit, static_argnames=("dt", "dx"))
def vacuum_step(fields, *, dt: float, dx):
    """The same field update with no current: what the fields would do
    with the plasma taken away."""
    zero = jnp.zeros_like(fields[0])
    return maxwell(fields, (zero, zero, zero), dt, dx)


def run(state: dict, n_steps: int, *, order: int, dt: float, charge: float, mass: float, dx):
    """Advance ``state`` (numpy arrays: ``fields`` (6 grids), ``pos``,
    ``u``, ``w``, ``alive``) by ``n_steps``. Returns the reference state
    and the vacuum fields after the same number of steps."""
    with jax.default_matmul_precision("highest"):
        fields = tuple(jnp.asarray(f, jnp.float32) for f in state["fields"])
        vac = fields
        pos = jnp.asarray(state["pos"], jnp.float32)
        u = jnp.asarray(state["u"], jnp.float32)
        w = jnp.asarray(state["w"], jnp.float32)
        alive = jnp.asarray(state["alive"], bool)
        dx = tuple(float(d) for d in dx)
        for _ in range(n_steps):
            fields, pos, u = step(fields, pos, u, w, alive, order=order, dt=dt,
                                  charge=charge, mass=mass, dx=dx)
            vac = vacuum_step(vac, dt=dt, dx=dx)
        out = jax.device_get({"fields": fields, "pos": pos, "u": u, "vacuum": vac})
    out["w"], out["alive"] = state["w"], state["alive"]
    return out
