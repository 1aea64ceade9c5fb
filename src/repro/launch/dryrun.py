import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")

"""Multi-pod dry-run (brief deliverable e): for every (architecture x input
shape x mesh), jit-lower and COMPILE the production step function with full
shardings, then record

  * compiled.memory_analysis()   -> proves the cell fits per-device HBM
  * compiled.cost_analysis()     -> HLO FLOPs / bytes for the roofline
  * collective bytes             -> parsed from the post-SPMD HLO text

Results are cached as JSON per cell under --out (default
benchmarks/dryrun_results/), consumed by benchmarks/roofline.py and
EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch whisper-tiny --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.registry import ARCH_IDS, SHAPES, cell_supported, get_config, input_specs  # noqa: E402
from repro.distributed.sharding import Rules, rules_for, use_rules  # noqa: E402
from repro.launch.flops import cell_costs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import decode_step, forward  # noqa: E402
from repro.models.transformer import decode_state_axes, param_axes  # noqa: E402
from repro.train import TrainConfig, init_train_state, make_train_step  # noqa: E402

_IS_AXES = lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)

# collective-traffic factors (ring algorithms), bytes-on-link per result byte
_COLLECTIVE_FACTORS = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo_text: str):
    """Split HLO text into computation blocks. Returns (blocks, entry)."""
    blocks: dict[str, list[str]] = {}
    entry = None
    cur = None
    for raw in hlo_text.splitlines():
        s = raw.strip()
        if s.endswith("{") and ("(" in s and "->" in s or s.startswith("ENTRY")):
            m = re.match(r"(ENTRY\s+)?%?([\w.\-]+)", s)
            if m:
                cur = m.group(2)
                blocks[cur] = []
                if m.group(1):
                    entry = cur
            continue
        if s == "}":
            cur = None
            continue
        if cur is not None:
            blocks[cur].append(s)
    return blocks, entry


_WHILE_RE = re.compile(r"while\(.*?\),\s*condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective, MULTIPLIED by enclosing
    loop trip counts (XLA's cost/HLO view counts a while body once; a
    collective inside the 56-period layer scan really runs 56x). Trip counts
    are read from the loop-condition constants (scan loops compare the
    induction variable against a literal)."""
    blocks, entry = _split_computations(hlo_text)

    def trip_count(cond_name: str) -> int:
        consts = [int(x) for line in blocks.get(cond_name, ()) for x in _TRIP_RE.findall(line)]
        return max(consts) if consts else 1

    from functools import lru_cache

    @lru_cache(maxsize=None)
    def block_totals(name: str):
        totals = {k: [0, 0] for k in _COLLECTIVE_FACTORS}  # op -> [count, bytes]
        for line in blocks.get(name, ()):
            m = re.match(r"(?:ROOT )?%?[\w.\-]+\s*=\s*(.*)$", line)
            if m is None:
                continue
            rhs = m.group(1)
            wm = _WHILE_RE.search(rhs)
            if wm:
                trips = trip_count(wm.group(1))
                inner = block_totals(wm.group(2))
                for k in totals:
                    totals[k][0] += trips * inner[k][0]
                    totals[k][1] += trips * inner[k][1]
                continue
            # follow calls/fusions into sub-computations
            cm = re.search(r"(?:calls|to_apply)=%?([\w.\-]+)", rhs)
            if cm and cm.group(1) in blocks:
                inner = block_totals(cm.group(1))
                for k in totals:
                    totals[k][0] += inner[k][0]
                    totals[k][1] += inner[k][1]
            for op in _COLLECTIVE_FACTORS:
                if re.search(rf"\s{op}(?:-start)?\(", rhs) or rhs.startswith(f"{op}("):
                    totals[op][0] += 1
                    totals[op][1] += _shape_bytes(rhs.split(op)[0])
                    break
        return {k: tuple(v) for k, v in totals.items()}

    agg = block_totals(entry) if entry else {k: (0, 0) for k in _COLLECTIVE_FACTORS}
    out = {k: {"count": agg[k][0], "bytes": agg[k][1]} for k in _COLLECTIVE_FACTORS}
    out["link_bytes"] = sum(int(v["bytes"] * _COLLECTIVE_FACTORS[k]) for k, v in out.items() if isinstance(v, dict))
    out["total_bytes"] = sum(v["bytes"] for v in out.values() if isinstance(v, dict))
    return out


def _shardings(tree_axes, tree_shapes, rules: Rules, mesh):
    """Logical axes -> NamedShardings for jit in_shardings. Argument
    shardings must divide evenly (unlike internal constraints), so any
    uneven dim falls back to replicated for the *argument* only."""
    import math

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(axes, shp):
        dims = []
        for i, ax in enumerate(axes):
            m = rules.table.get(ax) if ax is not None else None
            if m is None:
                dims.append(None)
                continue
            prod = sizes[m] if isinstance(m, str) else math.prod(sizes[a] for a in m)
            dims.append(m if shp.shape[i] % prod == 0 else None)
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(one, tree_axes, tree_shapes, is_leaf=_IS_AXES)


def _batch_axes(specs: dict) -> dict:
    out = {}
    for k, v in specs.items():
        if k in ("inputs", "targets", "tokens", "mask"):
            out[k] = ("batch", None)
        elif k in ("frames", "prefix_embeddings", "enc_out"):
            out[k] = ("batch", None, None)
        else:
            raise KeyError(k)
    return out


def build_cell(arch: str, shape_name: str, *, multi_pod: bool):
    """Returns (fn, example_args (ShapeDtypeStructs), in_shardings, rules)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_size = 16 * (2 if multi_pod else 1)
    shard_batch = shape.global_batch % data_size == 0

    mode = "train" if shape.kind == "train" else "decode"
    rules = Rules(
        rules_for(cfg, mode=mode, multi_pod=multi_pod, shard_batch=shard_batch), mesh
    )
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        # 4-way gradient accumulation everywhere: §Perf L8 measured that
        # dropping it saves only ~10% collective traffic (the traffic is
        # dominated by MoE-dispatch resharding and TP all-reduces, NOT the
        # per-microbatch ZeRO param gathers) while costing 2.4x HBM.
        train_step = make_train_step(cfg, TrainConfig(microbatches=4))
        state_shapes = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg))
        pax = param_axes(cfg)
        state_axes = {
            "params": pax,
            "opt": {"mu": pax, "nu": pax, "count": ()},
            "step": (),
        }
        in_shardings = (
            _shardings(state_axes, state_shapes, rules, mesh),
            _shardings(_batch_axes(specs), specs, rules, mesh),
        )
        args = (state_shapes, specs)
        return train_step, args, in_shardings, rules, mesh, cfg

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            kwargs = {k: batch[k] for k in ("frames", "prefix_embeddings") if k in batch}
            logits = forward(params, batch["inputs"], cfg, remat=False, **kwargs)
            return logits[:, -1, :]  # next-token logits (cache write covered by decode cells)

        params_shapes = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg))["params"]
        in_shardings = (
            _shardings(param_axes(cfg), params_shapes, rules, mesh),
            _shardings(_batch_axes(specs), specs, rules, mesh),
        )
        return prefill_step, (params_shapes, specs), in_shardings, rules, mesh, cfg

    # decode
    def serve_step(params, state, batch):
        enc_out = batch.get("enc_out")
        logits, new_state = decode_step(params, state, batch["tokens"], cfg, enc_out=enc_out)
        return jnp.argmax(logits[:, -1], axis=-1), new_state

    params_shapes = jax.eval_shape(lambda: init_train_state(jax.random.PRNGKey(0), cfg))["params"]
    state_specs = specs["state"]
    saxes = decode_state_axes(cfg)
    batch_specs = {k: v for k, v in specs.items() if k != "state"}
    in_shardings = (
        _shardings(param_axes(cfg), params_shapes, rules, mesh),
        _shardings(saxes, state_specs, rules, mesh),
        _shardings(_batch_axes(batch_specs), batch_specs, rules, mesh),
    )
    return serve_step, (params_shapes, state_specs, batch_specs), in_shardings, rules, mesh, cfg


def run_cell(arch: str, shape_name: str, *, multi_pod: bool) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    supported, reason = cell_supported(arch, shape_name)
    if not supported:
        record["skipped"] = reason
        return record

    fn, args, in_shardings, rules, mesh, cfg = build_cell(arch, shape_name, multi_pod=multi_pod)
    record["params_b"] = cfg.param_count() / 1e9

    with jax.set_mesh(mesh), use_rules(rules):
        t0 = time.time()
        lowered = jax.jit(fn, in_shardings=in_shardings).lower(*args)
        record["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t0, 2)

    mem = compiled.memory_analysis()
    if mem is not None:
        for field in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            v = getattr(mem, field, None)
            if v is not None:
                record[field] = int(v)
        args_b = record.get("argument_size_in_bytes", 0)
        alias_b = record.get("alias_size_in_bytes", 0)
        out_b = record.get("output_size_in_bytes", 0)
        tmp_b = record.get("temp_size_in_bytes", 0)
        record["hbm_per_device_gb"] = round((args_b + out_b + tmp_b - alias_b) / 2**30, 3)

    cost = compiled.cost_analysis()
    if cost:
        # NOTE: XLA counts while-loop bodies once; these raw numbers
        # under-report scanned models and are kept for reference only.
        record["hlo_flops_oncecount"] = float(cost.get("flops", 0.0))
        record["hlo_bytes_oncecount"] = float(cost.get("bytes accessed", 0.0))

    chips = 512 if multi_pod else 256
    analytic = cell_costs(cfg, SHAPES[shape_name], chips)
    record["flops"] = analytic["flops"]            # per chip, loop-corrected
    record["bytes_accessed"] = analytic["bytes"]   # per chip, loop-corrected

    record["collectives"] = parse_collectives(compiled.as_text())
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/dryrun_results")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = list(ARCH_IDS) if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    os.makedirs(args.out, exist_ok=True)
    for arch, shape_name, multi_pod in cells:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        path = os.path.join(args.out, f"{arch}__{shape_name}__{mesh_name}.json")
        if os.path.exists(path) and not args.force:
            print(f"[skip cached] {path}")
            continue
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name} ...", flush=True)
        try:
            record = run_cell(arch, shape_name, multi_pod=multi_pod)
        except Exception as exc:  # noqa: BLE001 — record failures, keep sweeping
            record = {
                "arch": arch, "shape": shape_name, "mesh": mesh_name,
                "error": f"{type(exc).__name__}: {exc}",
                "traceback": traceback.format_exc()[-4000:],
            }
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        status = "SKIP" if "skipped" in record else ("FAIL" if "error" in record else "ok")
        extra = record.get("error", record.get("skipped", ""))[:120]
        print(
            f"[{status}] {arch} x {shape_name} x {mesh_name} "
            f"hbm={record.get('hbm_per_device_gb', '?')}GB "
            f"compile={record.get('compile_s', '?')}s {extra}",
            flush=True,
        )


if __name__ == "__main__":
    main()
