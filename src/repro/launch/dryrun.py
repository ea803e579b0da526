import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "10")

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and record roofline inputs.

The lines above MUST stay first: jax locks the device count on first
init, and the dry-run needs 512 placeholder host devices for the production
meshes. (Smoke tests / benches import repro normally and see 1 device.)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
      --shape train_4k --mesh pod1            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh pod1|pod2]
  PYTHONPATH=src python -m repro.launch.dryrun --list

Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json with
  flops / bytes_accessed (per-device × chips), collective_bytes by op,
  memory_analysis, model_flops — consumed by benchmarks/roofline.py.
"""

import argparse
import json
import pathlib
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.distributed.sharding import (
    batch_specs,
    cache_specs,
    make_shardings,
    moment_specs,
    param_specs,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.costs import cost_dict
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import batch_specs_for, model_flops, model_state_specs
from repro.models import make_decode_step, make_prefill_step, make_train_step

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(shape_str: str) -> int:
    """bytes of an HLO result type like 'bf16[16,128,512]' (tuples summed)."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def entry_text(hlo_text: str) -> str:
    """The ENTRY computation only (nested fusion/while bodies excluded) —
    counting nested lines would double-count fused internals."""
    m = re.search(r"^ENTRY [^{]*\{", hlo_text, re.M)
    if not m:
        return hlo_text
    start = m.end()
    depth = 1
    i = start
    while i < len(hlo_text) and depth:
        c = hlo_text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        i += 1
    return hlo_text[start:i]


def hlo_bytes_by_op(hlo_text: str, top: int = 14) -> dict:
    """Result-shape bytes per op kind over the ENTRY computation.

    Approximates per-device HBM writes: each surviving top-level op's output
    is materialized once; fusion internals are excluded (they live in
    registers/VMEM on TPU). Backend note (EXPERIMENTS.md §Roofline): XLA
    *cost_analysis* on CPU additionally counts elementwise chains that a TPU
    compile would fuse — we record both and derive the memory term from the
    entry-only structural estimate.
    """
    per_op: dict[str, int] = {}
    for line in entry_text(hlo_text).splitlines():
        s = line.strip()
        m = re.search(r"=\s+(\([^)]*\)|\S+)\s+([a-z0-9-]+)\(", s)
        if not m:
            continue
        op = m.group(2)
        per_op[op] = per_op.get(op, 0) + _shape_bytes(m.group(1))
    return dict(sorted(per_op.items(), key=lambda kv: -kv[1])[:top])


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the (per-device)
    compiled HLO. all-gather results count the full gathered size (what the
    links move per device, ring-style); all-reduce counts the operand once
    (reduce-scatter + all-gather of the same payload ≈ 2×, noted in
    EXPERIMENTS.md)."""
    per_op: dict[str, int] = {}
    for line in entry_text(hlo_text).splitlines():
        s = line.strip()
        # ROOT x = bf16[...] all-reduce(...) / x = (bf16[..], ..) all-to-all(..)
        m = re.search(r"=\s+(\([^)]*\)|\S+)\s+([a-z0-9-]+)", s)
        if not m:
            continue
        op = m.group(2)
        if op.rstrip("-start").rstrip("-done") in _COLLECTIVES or op in _COLLECTIVES:
            base = op.replace("-start", "").replace("-done", "")
            if base not in _COLLECTIVES:
                continue
            if op.endswith("-done"):
                continue  # avoid double counting start/done pairs
            per_op[base] = per_op.get(base, 0) + _shape_bytes(m.group(1))
    per_op["total"] = sum(per_op.values())
    return per_op


def run_cell(arch: str, shape_name: str, mesh_name: str, verbose: bool = True,
             opts: dict | None = None) -> dict:
    shape = configs.get_shape(shape_name)
    cfg = configs.get(arch)
    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    num_devices = mesh.size
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "num_devices": num_devices, "status": "ok",
        "model_flops": model_flops(cfg, shape),
        "opts": opts or {},
    }
    t0 = time.time()
    # head_aware=1 (§Perf iter-4): head-divisibility-aware attention sharding
    model_opts = dict(opts or {})
    shard_cfg = cfg if model_opts.pop("head_aware", 0) else None
    state = model_state_specs(cfg, shape, opts=model_opts)
    model = state["model"]
    batch = batch_specs_for(cfg, shape)
    with mesh:
        b_specs = make_shardings(batch_specs(batch, mesh), mesh)
        if shape.kind == "train":
            ts = state["train_state"]
            import dataclasses as _dc

            p_spec = param_specs(ts.params, mesh, shard_cfg)
            lora_spec = param_specs(ts.lora, mesh, shard_cfg) if ts.lora is not None else None
            opt_spec = type(ts.opt)(
                m=moment_specs(ts.opt.m, mesh, shard_cfg),
                v=moment_specs(ts.opt.v, mesh, shard_cfg),
                step=jax.sharding.PartitionSpec(),
            )
            from repro.models.model import TrainState

            ts_spec = TrainState(
                params=p_spec, lora=lora_spec, opt=opt_spec,
                step=jax.sharding.PartitionSpec(),
            )
            ts_shard = make_shardings(ts_spec, mesh)
            step = make_train_step(model)
            lowered = jax.jit(
                step, in_shardings=(ts_shard, b_specs)
            ).lower(ts, batch)
        elif shape.kind == "prefill":
            p_shard = make_shardings(param_specs(state["params"], mesh, shard_cfg), mesh)
            l_shard = make_shardings(param_specs(state["lora"], mesh, shard_cfg), mesh)
            step = make_prefill_step(model)
            lowered = jax.jit(
                step, in_shardings=(p_shard, l_shard, b_specs)
            ).lower(state["params"], state["lora"], batch)
        else:  # decode / long_decode
            p_shard = make_shardings(param_specs(state["params"], mesh, shard_cfg), mesh)
            l_shard = make_shardings(param_specs(state["lora"], mesh, shard_cfg), mesh)
            c_shard = make_shardings(cache_specs(state["cache"], mesh), mesh)
            step = make_decode_step(model)
            lowered = jax.jit(
                step, in_shardings=(p_shard, l_shard, c_shard, b_specs)
            ).lower(state["params"], state["lora"], state["cache"], batch)
        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1
        ca = cost_dict(compiled)
        flops_dev = float(ca.get("flops", 0.0))
        bytes_dev = float(ca.get("bytes accessed", 0.0))
        rec["flops_per_device"] = flops_dev
        rec["bytes_per_device"] = bytes_dev
        rec["flops"] = flops_dev * num_devices
        rec["bytes_accessed"] = bytes_dev * num_devices
        ma = compiled.memory_analysis()
        if ma is not None:
            for attr in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
            ):
                v = getattr(ma, attr, None)
                if v is not None:
                    rec[attr] = int(v)
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec["collective_bytes_per_device"] = coll
        rec["collective_bytes"] = coll.get("total", 0)
        rec["bytes_by_op"] = hlo_bytes_by_op(hlo)
        # structural HBM-traffic floor: entry-level op outputs + one read of
        # every argument (params/caches). TPU-realistic; see docstring above.
        rec["bytes_entry_per_device"] = (
            sum(rec["bytes_by_op"].values())
            + rec.get("argument_size_in_bytes", 0)
        )
        if verbose:
            print(compiled.memory_analysis())  # proves the cell fits
            print({k: v for k, v in cost_dict(compiled).items()
                   if k in ("flops", "bytes accessed", "transcendentals")})
            print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: "
                  f"compile={rec['compile_s']:.1f}s "
                  f"flops/dev={flops_dev:.3e} bytes/dev={bytes_dev:.3e} "
                  f"coll/dev={coll.get('total',0):.3e}B")
            print(f"[dryrun]   memory: args={rec.get('argument_size_in_bytes',0)/2**30:.2f}GiB "
                  f"out={rec.get('output_size_in_bytes',0)/2**30:.2f}GiB "
                  f"temp={rec.get('temp_size_in_bytes',0)/2**30:.2f}GiB per device")
    return rec


def cell_path(arch: str, shape: str, mesh: str) -> pathlib.Path:
    return RESULTS / f"{arch}__{shape}__{mesh}.json"


def all_cells(mesh_filter=None):
    cells = []
    for arch in configs.ARCH_IDS:
        cfg = configs.get(arch)
        for shape in configs.shape_cells(arch):
            for mesh in ("pod1", "pod2"):
                if mesh_filter and mesh != mesh_filter:
                    continue
                # cheapest-first: progress accumulates early, big/fragile
                # cells (MoE train) land last
                kind_cost = {"decode": 0, "long_decode": 1, "prefill": 2,
                             "train": 3}[shape.kind]
                cost = cfg.num_params() * (1 + kind_cost)
                cells.append((cost, arch, shape.name, mesh))
    cells.sort(key=lambda c: (c[0],))
    for _, arch, shape, mesh in cells:
        yield arch, shape, mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=("pod1", "pod2", "both"), default="pod1")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--opt", default="",
                    help="§Perf knobs, e.g. 'q_chunk=2048,remat_policy=dots'")
    ap.add_argument("--tag", default="",
                    help="suffix for the result file (optimized variants)")
    args = ap.parse_args()
    enable_compile_cache()
    opts: dict = {}
    for kv in args.opt.split(","):
        if not kv:
            continue
        k, v = kv.split("=")
        opts[k] = int(v) if v.lstrip("-").isdigit() else v
    RESULTS.mkdir(parents=True, exist_ok=True)
    if args.list:
        for cell in all_cells():
            done = cell_path(*cell).exists()
            print(("DONE " if done else "todo ") + "__".join(cell))
        return
    if args.all:
        mesh_filter = None if args.mesh == "both" else args.mesh
        cells = list(all_cells(mesh_filter))
        for arch, shape, mesh in cells:
            p = cell_path(arch, shape, mesh)
            if p.exists() and not args.force:
                continue
            try:
                rec = run_cell(arch, shape, mesh)
            except Exception as e:  # record failures as first-class results
                rec = {
                    "arch": arch, "shape": shape, "mesh": mesh,
                    "status": f"error: {type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
                print(f"[dryrun] FAILED {arch}×{shape}×{mesh}: {e}")
            p.write_text(json.dumps(rec, indent=1))
        return
    rec = run_cell(args.arch, args.shape, args.mesh, opts=opts)
    suffix = f"__{args.tag}" if args.tag else ""
    path = RESULTS / f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json"
    path.write_text(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
