"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input-shape x mesh) cell against the production meshes and
record memory/cost/collective analysis for the roofline (deliverable g).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_5_32b \
      --shape train_4k --mesh single --out results/dryrun
Each cell's record is persisted to results/dryrun/<cell>.json (resumable).
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import/init: jax locks the device count on first use.

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs as C
from repro.launch import shardings as SH
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.train import optim, step as STEP

ENC_FRAMES = 1500  # whisper 30 s stub frontend

_DT = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
       "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
       "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
         "collective-permute")


def _shape_bytes(stype: str) -> int:
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", stype)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DT.get(dt, 4)


def hlo_collective_bytes(text: str) -> dict:
    """Sum output bytes of every collective op in (partitioned) HLO text."""
    out = {k: {"bytes": 0, "count": 0} for k in _COLL}
    pat = re.compile(
        r"=\s+(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s+"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
    for m in pat.finditer(text):
        types, op = m.groups()
        if types.startswith("("):
            parts = re.findall(r"[a-z0-9]+\[[0-9,]*\]", types)
        else:
            parts = [types]
        out[op]["bytes"] += sum(_shape_bytes(p) for p in parts)
        out[op]["count"] += 1
    return out


def input_specs(arch: str, shape) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg = C.get_config(arch)
    sname, seq, gbs, kind = shape
    S = jax.ShapeDtypeStruct
    if kind == "train":
        batch = {"tokens": S((gbs, seq), jnp.int32),
                 "labels": S((gbs, seq), jnp.int32)}
    elif kind == "prefill":
        batch = {"tokens": S((gbs, seq), jnp.int32)}
    else:  # decode
        batch = {"tokens": S((gbs, 1), jnp.int32)}
    if cfg.family == "vlm" and kind != "decode":
        batch["prefix_embed"] = S((gbs, cfg.n_patches, cfg.d_model), cfg.dtype)
    if cfg.family == "encdec":
        batch["enc_frames"] = S((gbs, ENC_FRAMES, cfg.d_model), cfg.dtype)
    return batch


def _shard(mesh, spec_tree, shape_tree):
    return jax.tree.map(
        lambda sp, _: NamedSharding(mesh, sp), spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape, multi_pod: bool, microbatch: int = 0,
               cfg_override=None, tp_align: bool = False,
               fsdp: bool = False):
    """Build + lower one (arch x shape x mesh) cell; returns (lowered, cfg).

    ``cfg_override`` lets the roofline hillclimb lower modified configs
    (different sharding mode, remat policy, ...) through the same path.
    ``tp_align`` pads GQA heads for clean head-sharded TP (tp_align.py);
    ``fsdp`` ZeRO-shards params+optimizer over the data axes."""
    sname, seq, gbs, kind = shape
    cfg = cfg_override or C.get_config(arch)
    if tp_align and cfg_override is None:
        from repro.models import tp_align as TA
        cfg = TA.aligned(cfg, tp=16)
        cfg_override = cfg if cfg is not C.get_config(arch) else None
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.models.common import set_shard_ctx
    set_shard_ctx(dp_axes=("pod", "data") if multi_pod else ("data",),
                  tp_axis="model", mesh=mesh)
    params_shape = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    pspecs = SH.param_specs(cfg, params_shape, mesh, fsdp=fsdp)
    batch = input_specs(arch, shape)
    if cfg_override is not None:  # re-derive inputs for the modified cfg
        batch = _input_specs_cfg(cfg, shape)
    bspecs = SH.batch_specs(cfg, mesh, batch=gbs, kind=kind)
    bspecs = {k: bspecs.get(k, P(*([None] * len(v.shape))))
              for k, v in batch.items()}

    with mesh:
        if kind == "train":
            opt_shape = jax.eval_shape(
                lambda: optim.adamw_init(params_shape))
            ospecs = optim.AdamWState(
                m=jax.tree.map(lambda _, s: s, opt_shape.m,
                               SH.param_specs(cfg, opt_shape.m, mesh,
                                              fsdp=fsdp)),
                v=SH.param_specs(cfg, opt_shape.v, mesh, fsdp=fsdp),
                step=P(), err=None)
            fn = STEP.make_train_step(cfg, microbatch=microbatch)
            jf = jax.jit(
                fn,
                in_shardings=(_shard(mesh, pspecs, params_shape),
                              _shard(mesh, ospecs, opt_shape),
                              _shard(mesh, bspecs, batch)),
                out_shardings=(_shard(mesh, pspecs, params_shape),
                               _shard(mesh, ospecs, opt_shape), None),
                donate_argnums=(0, 1))
            lowered = jf.lower(params_shape, opt_shape, batch)
        elif kind == "prefill":
            fn = STEP.make_prefill_step(cfg, max_len=seq)
            jf = jax.jit(fn, in_shardings=(
                _shard(mesh, pspecs, params_shape),
                _shard(mesh, bspecs, batch)))
            lowered = jf.lower(params_shape, batch)
        else:  # decode
            cache_shape = jax.eval_shape(
                lambda: lm.init_cache(cfg, gbs, seq))
            cspec_fn = SH.cache_specs(cfg, mesh, batch=gbs, max_len=seq)
            cspecs = jax.tree_util.tree_map_with_path(cspec_fn, cache_shape)
            fn = STEP.make_serve_step(cfg)
            jf = jax.jit(
                fn,
                in_shardings=(_shard(mesh, pspecs, params_shape),
                              _shard(mesh, cspecs, cache_shape),
                              _shard(mesh, bspecs, batch)),
                out_shardings=(None, _shard(mesh, cspecs, cache_shape)),
                donate_argnums=(1,))
            lowered = jf.lower(params_shape, cache_shape, batch)
    return lowered, cfg, mesh


def _input_specs_cfg(cfg, shape) -> dict:
    """input_specs against an explicit (possibly modified) config."""
    sname, seq, gbs, kind = shape
    S = jax.ShapeDtypeStruct
    if kind == "train":
        batch = {"tokens": S((gbs, seq), jnp.int32),
                 "labels": S((gbs, seq), jnp.int32)}
    elif kind == "prefill":
        batch = {"tokens": S((gbs, seq), jnp.int32)}
    else:
        batch = {"tokens": S((gbs, 1), jnp.int32)}
    if cfg.family == "vlm" and kind != "decode":
        batch["prefix_embed"] = S((gbs, cfg.n_patches, cfg.d_model), cfg.dtype)
    if cfg.family == "encdec":
        batch["enc_frames"] = S((gbs, ENC_FRAMES, cfg.d_model), cfg.dtype)
    return batch


def run_cell(arch: str, shape, multi_pod: bool, out_dir: Path,
             microbatch: int = 0, force: bool = False,
             tp_align: bool = False, fsdp: bool = False) -> dict:
    sname, seq, gbs, kind = shape
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}__{sname}__{mesh_name}"
    out_file = out_dir / f"{cell}.json"
    if out_file.exists() and not force:
        return json.loads(out_file.read_text())

    t0 = time.time()
    rec = {"cell": cell, "arch": arch, "shape": sname, "mesh": mesh_name,
           "kind": kind, "seq": seq, "batch": gbs}
    try:
        with_mesh = True
        lowered, cfg, mesh = lower_cell(arch, shape, multi_pod,
                                        microbatch=microbatch,
                                        tp_align=tp_align, fsdp=fsdp)
        with mesh:
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)

            ma = compiled.memory_analysis()
            if ma is not None:
                for f in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes"):
                    v = getattr(ma, f, None)
                    if v is not None:
                        rec[f] = int(v)
            ca = compiled.cost_analysis() or {}
            rec["flops"] = float(ca.get("flops", -1))
            rec["bytes_accessed"] = float(ca.get("bytes accessed", -1))
            text = compiled.as_text()
            rec["collectives"] = hlo_collective_bytes(text)
            rec["hlo_chars"] = len(text)
        rec["ok"] = True
    except Exception as e:  # record failures — they are bugs to fix
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_file.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--tp-align", action="store_true",
                    help="pad GQA heads for clean head-sharded TP")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-shard params+optimizer over the data axes")
    args = ap.parse_args()

    out_dir = Path(args.out)
    archs = [args.arch] if args.arch else C.ARCHS
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape, skip in C.arch_shapes(arch):
            if args.shape and shape[0] != args.shape:
                continue
            if skip:
                for mp in meshes:
                    mesh_name = "pod2x16x16" if mp else "pod16x16"
                    cell = f"{arch}__{shape[0]}__{mesh_name}"
                    (out_dir / f"{cell}.json").parent.mkdir(parents=True,
                                                            exist_ok=True)
                    (out_dir / f"{cell}.json").write_text(json.dumps(
                        {"cell": cell, "ok": True, "skipped": skip}))
                    print(f"SKIP {cell}: {skip}")
                    n_skip += 1
                continue
            for mp in meshes:
                rec = run_cell(arch, shape, mp, out_dir, force=args.force,
                               microbatch=args.microbatch,
                               tp_align=args.tp_align, fsdp=args.fsdp)
                if rec.get("skipped"):
                    n_skip += 1
                    continue
                status = "OK" if rec["ok"] else "FAIL"
                n_ok += rec["ok"]
                n_fail += not rec["ok"]
                print(f"{status} {rec['cell']} "
                      f"flops={rec.get('flops', 0):.3g} "
                      f"temp={rec.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
                      f"({rec.get('total_s', 0)}s)"
                      + ("" if rec["ok"] else f" :: {rec.get('error')}"),
                      flush=True)
    print(f"dry-run complete: ok={n_ok} skip={n_skip} fail={n_fail}")
    return n_fail


if __name__ == "__main__":
    raise SystemExit(main())
