"""Reduction of a profiler trace to device busy time, per-layer device time
and idle gaps.

A traced run writes JAX's profile (``.xplane.pb``). From it this module
takes, for each device used, the intervals of the operations on its
``XLA Ops`` line and of the executables on its ``XLA Modules`` line, and
from the host the benchmark's own spans. On a TPU an op's event is named
by its HLO instruction (``%fusion.12 = s32[8]{0} fusion(...)``) and
carries no source metadata, so the op's text for attribution joins that
instruction to the compiled window's HLO (`hlo_metadata`): its ``op_name``
path (the nested ``jit(...)`` names) and its Python stack. The join holds
only for ops of the executable that the HLO text is of: an op belongs to
the module whose interval holds it, and ops of the window's module must
each name an instruction of that HLO with the same result shape and
opcode (`check_module`), or the reduction fails; ops of any other module
are left unclaimed. Control-flow ops nest: a ``while`` op's event spans
its body's ops, so an op's time is its self time (its duration less its
children's). It reports:

* ``window_s``: the traced window, from the first ``bench.window`` span's
  start to the last one's end;
* ``busy_s``: the union of the device's op intervals inside the window,
  averaged over the devices (a fusion's own time between the ops nested
  in it is busy time);
* ``layer_s``: device seconds per layer (averaged over the devices). An op
  goes to the first layer, in the order of `run.load_layers`, one of whose
  regular expressions matches the op's text: its name, then its HLO
  ``op_name`` path and source-file metadata;
* ``unclaimed_share``: the share of op time that no layer claims, and the
  op names in it;
* the device ops that took most time, and the longest idle gaps with the
  host span (innermost) that covers each.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: host threads whose spans say what the host was doing: the Python
#: thread (the benchmark's annotations) and the main runtime thread
HOST_LINES = ("python", "main")
WINDOW_SPAN = "bench.window"
TOP = 10


INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
FRAME_ID = re.compile(r'metadata=\{[^}]*stack_frame_id=(\d+)')
TABLE_ROW = re.compile(r"^(\d+) (.*)$")
FIELD = re.compile(r"(\w+)=(\d+)")
MAX_DEPTH = 256
#: ``%name = <result shape> <opcode>(``, as HLO text and trace events print an instruction
SIGNATURE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+([\w\-]+)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")
MODULE_SUFFIX = re.compile(r"\(\d+\)$")
#: the label of ops that ran in another executable than the window's
FOREIGN = "other module"


@dataclasses.dataclass
class Op:
    start: float  # ns
    end: float
    name: str  # HLO instruction name
    text: str  # name and metadata, matched by the layer patterns
    self_ns: float = -1.0  # duration less the ops nested in it
    module: str = ""  # the executable the op ran in
    event: str = ""  # the trace event's own text: the instruction as printed


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    m = INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name.split(" ", 1)[0].lstrip("%")


def signature(text: str) -> tuple[str, str, str] | None:
    """(name, result shape without layouts, opcode) of an instruction as
    HLO text or a trace event prints it; None where it is cut short."""
    m = SIGNATURE.match(text)
    if not m:
        return None
    return m.group(1), LAYOUT.sub("", m.group(2)).replace(" ", ""), m.group(3)


def module_name(event_name: str) -> str:
    """``jit__pic_run_window_impl(8178958833973486692)`` -> the module's name."""
    return MODULE_SUFFIX.sub("", event_name.strip())


def hlo_module_name(hlo_text: str) -> str:
    """The name in an HLO text's ``HloModule <name>, ...`` header."""
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            return line.split()[1].rstrip(",")
    raise ValueError("the HLO text has no HloModule header")


def hlo_signatures(hlo_text: str) -> dict[str, tuple[str, str]]:
    """{instruction name: (result shape without layouts, opcode)}."""
    out = {}
    for line in hlo_text.splitlines():
        sig = signature(line)
        if sig:
            out[sig[0]] = sig[1:]
    return out


def check_module(ops: list[Op], module: str, hlo_text: str) -> int:
    """Fail unless the ops of ``module`` in the trace are instructions of
    ``hlo_text``: each op's name is an instruction there, with the same
    result shape and opcode where the event prints them. Returns the
    number of ops checked."""
    sigs = hlo_signatures(hlo_text)
    mine = [o for o in ops if o.module == module]
    if not mine:
        raise ValueError(f"the trace holds no op of the module {module!r}")
    bad = []
    for o in mine:
        if o.name not in sigs:
            bad.append(f"{o.name}: not in the recompiled module")
            continue
        sig = signature(o.event)
        if sig is not None and sig[1:] != sigs[o.name]:
            bad.append(f"{o.name}: traced {sig[1:]}, recompiled {sigs[o.name]}")
    if bad:
        raise ValueError(f"{len(bad)} of {len(mine)} ops of {module!r} in the trace do not match "
                         f"the recompiled window module, e.g. {sorted(set(bad))[:5]}")
    return len(mine)


def _tables(hlo_text: str) -> dict[str, dict[int, str]]:
    """The module's source tables (``FileNames``, ``FunctionNames``,
    ``FileLocations``, ``StackFrames``): {table: {id: row}}."""
    tables: dict[str, dict[int, str]] = {}
    current = None
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            current = tables.setdefault(line, {})
            continue
        m = TABLE_ROW.match(line) if current is not None else None
        if m:
            current[int(m.group(1))] = m.group(2)
        elif current is not None and line.strip():
            current = None
    return tables


def _stacks(tables: dict) -> dict[int, list[str]]:
    """{stack frame id: ["file:function", ...] innermost first}. A frame's
    printed ``parent_frame_id`` is one more than its parent's id; 0 ends
    the chain."""
    names = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    funcs = {k: v.strip('"') for k, v in tables.get("FunctionNames", {}).items()}
    locs = {}
    for k, row in tables.get("FileLocations", {}).items():
        f = dict((a, int(b)) for a, b in FIELD.findall(row))
        locs[k] = f"{names.get(f.get('file_name_id'), '?')}:{funcs.get(f.get('function_name_id'), '?')}"
    frames = {k: dict((a, int(b)) for a, b in FIELD.findall(row))
              for k, row in tables.get("StackFrames", {}).items()}
    out = {}
    for fid in frames:
        chain, cur = [], fid
        while cur in frames and len(chain) < MAX_DEPTH:
            chain.append(locs.get(frames[cur].get("file_location_id"), "?"))
            parent = frames[cur].get("parent_frame_id", 0) - 1
            if parent == cur:
                break
            cur = parent
        out[fid] = chain
    return out


def hlo_metadata(hlo_text: str) -> dict[str, str]:
    """{instruction name: its op_name path, then its Python stack
    ("file:function", innermost first), one per line} from a compiled
    module's HLO text."""
    stacks = _stacks(_tables(hlo_text))
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if not m or "metadata=" not in line:
            continue
        op = OP_NAME.search(line)
        fid = FRAME_ID.search(line)
        frames = stacks.get(int(fid.group(1)), []) if fid else []
        out[m.group(1)] = "\n".join([op.group(1) if op else ""] + frames)
    return out


def nest(ops: list[Op]) -> list[Op]:
    """Set each op's self time from how the intervals on one device line
    nest."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    stack: list[Op] = []
    for o in ops:
        o.self_ns = o.end - o.start
        while stack and o.start >= stack[-1].end:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent.self_ns -= max(0.0, min(o.end, parent.end) - o.start)
        stack.append(o)
    return ops


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    layer_s: dict
    unclaimed_share: float
    unclaimed_top: list
    top_ops: list
    idle_gaps: list

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops[:TOP], "idle_gaps": self.idle_gaps[:TOP]}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def compile_layers(layers: list[dict]) -> list[tuple[str, list[re.Pattern]]]:
    return [(l["key"], [re.compile(p) for p in l["patterns"]]) for l in layers]


def attribute(text: str, layers) -> str | None:
    """The layer of an op: its text is read line by line (the instruction
    and op_name path, then the stack innermost first), and the first line
    any layer's pattern matches decides, layers tried in priority order."""
    for line in text.split("\n"):
        for key, patterns in layers:
            if any(p.search(line) for p in patterns):
                return key
    return None


def reduce_events(device_ops: dict, host_spans: list, layers: list[dict]) -> Reduction:
    """``device_ops``: {device: [Op]}; ``host_spans``: [(start, end, name)]
    in the same clock (ns). Layers as `run.load_layers` gives them."""
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    compiled = compile_layers(layers)
    n_dev = max(1, len(device_ops))

    layer_ns: dict = defaultdict(float)
    op_ns: dict = defaultdict(float)
    unclaimed: dict = defaultdict(float)
    claims: dict = {}
    busy_ns = total_ns = 0.0
    gaps = []
    for dev, ops in device_ops.items():
        ops = [o for o in nest(ops) if o.end > lo and o.start < hi]
        busy = union(clip([(o.start, o.end) for o in ops], lo, hi))
        busy_ns += length(busy)
        for o in ops:
            # the self time inside the window, in proportion for an op cut by an edge
            d = o.self_ns * (min(o.end, hi) - max(o.start, lo)) / max(o.end - o.start, 1e-9)
            total_ns += d
            if o.text not in claims:
                claims[o.text] = None if o.module == FOREIGN else attribute(o.text, compiled)
            key = claims[o.text]
            op_ns[_label(o, key)] += d
            if key is None:
                unclaimed[_label(o, None)] += d
            else:
                layer_ns[key] += d
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for k in range(0, len(edges), 2):
            if edges[k + 1] > edges[k]:
                gaps.append((edges[k], edges[k + 1]))

    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_host_doing(host_spans, (s + e) / 2), (e - s) * 1e-9] for s, e in gaps[:TOP]]
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns / n_dev * 1e-9,
        layer_s={k: v / n_dev * 1e-9 for k, v in layer_ns.items()},
        unclaimed_share=(sum(unclaimed.values()) / total_ns) if total_ns else 0.0,
        unclaimed_top=[[n, v / n_dev * 1e-9] for n, v in
                       sorted(unclaimed.items(), key=lambda kv: -kv[1])[:TOP * 2]],
        top_ops=[[n, v / n_dev * 1e-9] for n, v in top_ops],
        idle_gaps=idle,
    )


def _label(op: Op, layer: str | None) -> str:
    """``fusion.12 (deposition: .../dot_general)``: the instruction, the
    layer that claimed it, and the tail of its op_name path."""
    if op.module == FOREIGN:
        return f"{op.text} (unclaimed: {FOREIGN})"
    first = op.text.split("\n", 1)[0].split(" ")
    path = first[1].split("/") if len(first) > 1 and first[1] else []
    return f"{op.name} ({layer or 'unclaimed'}: {'/'.join(path[-2:])})"


def _host_doing(host_spans, t: float) -> str:
    """The innermost host span that covers time ``t``."""
    best = None
    for s, e, n in host_spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "no host span"


def find_xplane(directory: Path) -> Path:
    files = sorted(Path(directory).glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def assign_modules(ops: list[Op], modules: list[tuple[float, float, str]]) -> None:
    """Set each op's module: the executable whose interval on the same
    device holds the op's start (the empty name where none does)."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    for o in ops:
        k = bisect.bisect_right(starts, o.start) - 1
        o.module = modules[k][2] if k >= 0 and o.start <= modules[k][1] else ""


def label_ops(ops: list[Op], module: str, meta: dict[str, str]) -> None:
    """The text an op is attributed by: for an op of ``module``, its name
    and its metadata from that module's HLO; any other op is marked as
    another module's and carries only its name."""
    for o in ops:
        if o.module == module:
            o.text = f"{o.name} {meta.get(o.name, '')}"
        else:
            o.text, o.module = f"{o.name} [{o.module or 'no module'}]", FOREIGN


def read_xplane(path: Path) -> tuple[dict, list]:
    """(device ops per device, host spans) from a profile file. Each op
    carries the module it ran in and its event's text."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device_ops: dict = {}
    host_spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = device_ops.setdefault(int(m.group(1)), [])
            modules = []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        start = float(ev.start_ns)
                        modules.append((start, start + float(ev.duration_ns), module_name(ev.name)))
                elif line.name == OPS_LINE:
                    for ev in line.events:
                        name = instruction_name(ev.name)
                        start = float(ev.start_ns)
                        ops.append(Op(start, start + float(ev.duration_ns), name, name, event=ev.name))
            assign_modules(ops, modules)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if not line.name.startswith(HOST_LINES):
                    continue
                for ev in line.events:
                    start = float(ev.start_ns)
                    host_spans.append((start, start + float(ev.duration_ns), ev.name))
    return device_ops, host_spans


def reduce(path: Path, layers: list[dict], n_devices: int, hlo_text: str) -> Reduction:
    """Reduce the profile at ``path``; ``hlo_text`` is the compiled window
    program's, whose module the trace's window ops must match."""
    device_ops, host_spans = read_xplane(path)
    used = {d: ops for d, ops in device_ops.items() if d < n_devices}
    if not used:
        raise ValueError(f"the trace {path} holds no ops of the first {n_devices} TPU device(s)")
    module, meta = hlo_module_name(hlo_text), hlo_metadata(hlo_text)
    for ops in used.values():
        check_module(ops, module, hlo_text)
        label_ops(ops, module, meta)
    return reduce_events(used, host_spans, layers)
