"""Pre-jax-import device plumbing for --mesh launchers (jax-free on purpose:
the host-platform device count can only be forced BEFORE jax initializes, so
launchers peek argv with these helpers and only then import jax)."""

from __future__ import annotations

import os
import sys


def parse_mesh(spec: str) -> tuple[int, int]:
    """Parse an SXxSY mesh spec ('4x2') with a clean error on bad input."""
    try:
        sx, sy = (int(v) for v in spec.lower().split("x"))
    except ValueError as e:
        raise SystemExit(f"--mesh expects SXxSY (e.g. 4x2), got {spec!r}") from e
    if sx < 1 or sy < 1:
        raise SystemExit(f"--mesh sizes must be positive, got {spec!r}")
    return sx, sy


def peek_mesh_argv(argv: list[str] | None = None) -> tuple[int, int] | None:
    """The --mesh value from argv, parsed, or None when absent."""
    argv = sys.argv if argv is None else argv
    spec = None
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            spec = argv[i + 1]
        elif a.startswith("--mesh="):
            spec = a.split("=", 1)[1]
    return parse_mesh(spec) if spec is not None else None


def peek_spec_mesh_argv(argv: list[str] | None = None) -> tuple[int, int] | None:
    """The mesh shape named by a ``--spec FILE.json`` SimSpec in argv, or
    None. Pure-JSON peek (no repro.api import, jax-free): like
    `peek_mesh_argv`, this must run BEFORE jax initializes so the launcher
    can force enough host devices for the spec's mesh. A missing/invalid
    file returns None here — argparse reports it properly later."""
    import json

    argv = sys.argv if argv is None else argv
    path = None
    for i, a in enumerate(argv):
        if a == "--spec" and i + 1 < len(argv):
            path = argv[i + 1]
        elif a.startswith("--spec="):
            path = a.split("=", 1)[1]
    if path is None:
        return None
    try:
        with open(path) as f:
            shape = json.load(f).get("mesh", {}).get("shape")
        if not shape:
            return None
        if isinstance(shape, str):  # MeshSpec also accepts the "SXxSY" form
            return parse_mesh(shape)  # the one SXxSY grammar, shared with --mesh
        sx, sy = (int(v) for v in shape)
        return (sx, sy)
    except (OSError, ValueError, TypeError, AttributeError, SystemExit):
        return None  # malformed spec: argparse/SimSpec.from_json report it properly later


def force_host_devices(n: int) -> None:
    """Force n emulated host-platform devices unless an override (real
    accelerators, or the user's own XLA_FLAGS) is already present. Must run
    before jax import."""
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n} " + flags


def device_count_without_jax() -> int:
    """The devices jax will see in this process, worked out WITHOUT importing
    jax — a parent that touches jax holds the chip, so a child it starts
    could no longer reach it. Counts the TPU chips the host exposes (one
    ``/dev/accel*`` or numbered ``/dev/vfio`` node per chip) unless
    ``JAX_PLATFORMS`` pins the CPU; else the host-platform device count
    forced through ``XLA_FLAGS`` (1 when none is forced)."""
    import glob
    import re

    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        vfio = [p for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()]
        chips = len(glob.glob("/dev/accel*")) or len(vfio)
        if chips:
            return chips
    forced = re.search(r"--xla_force_host_platform_device_count=(\d+)", os.environ.get("XLA_FLAGS", ""))
    return int(forced.group(1)) if forced else 1


def emulated_devices_env(n: int) -> dict:
    """Environment for a child process that runs on ``n`` emulated host
    devices: the CPU platform only, so it never contends for a chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n} " + env.get("XLA_FLAGS", "")
    return env
