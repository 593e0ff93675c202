"""Roofline analysis (deliverable g): three-term roofline per
(architecture x shape) cell on the single-pod production mesh.

    compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = collective_bytes_per_chip / link_bw

HLO terms come from the scan-aware analyzer
(``repro.launch.hlo_analysis``): XLA's ``cost_analysis()`` counts a
``while`` body once, so layer-scanned models under-report by ~n_layers;
the analyzer multiplies by each loop's ``known_trip_count``.  Both raw
and corrected values are recorded.

Usage:
  PYTHONPATH=src python -m benchmarks.roofline                  # all cells
  PYTHONPATH=src python -m benchmarks.roofline --cell granite_34b__train_4k
  PYTHONPATH=src python -m benchmarks.roofline --cell ... --attribute
  PYTHONPATH=src python -m benchmarks.roofline --table          # md table
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse
import json
import time
from pathlib import Path

# ---- TPU v5e hardware constants (per prompt) ----
PEAK_FLOPS = 197e12     # bf16 FLOP/s per chip
HBM_BW = 819e9          # bytes/s per chip
LINK_BW = 50e9          # bytes/s per ICI link
CHIPS = 256             # single-pod 16x16


def model_flops(cfg, shape) -> float:
    """6*N_active*D for train; 2*N_active*D forward-only (prefill/decode)."""
    sname, seq, gbs, kind = shape
    n = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n * gbs * seq
    if kind == "prefill":
        return 2.0 * n * gbs * seq
    return 2.0 * n * gbs  # decode: one token per sequence


def analyze_cell(arch: str, shape, out_dir: Path, *, force=False,
                 cfg_override=None, tag="", microbatch=0,
                 save_hlo=False) -> dict:
    from repro import configs as C
    from repro.launch import dryrun as DR
    from repro.launch import hlo_analysis as H

    sname = shape[0]
    cell = f"{arch}__{sname}" + (f"__{tag}" if tag else "")
    out_file = out_dir / f"{cell}.json"
    if out_file.exists() and not force:
        return json.loads(out_file.read_text())

    t0 = time.time()
    rec = {"cell": cell, "arch": arch, "shape": sname, "kind": shape[3]}
    lowered, cfg, mesh = DR.lower_cell(arch, shape, multi_pod=False,
                                       microbatch=microbatch,
                                       cfg_override=cfg_override)
    with mesh:
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    if save_hlo:
        (out_dir / f"{cell}.hlo.txt").write_text(text)
    a = H.analyze(text)

    rec["flops_raw"] = float(ca.get("flops", -1))
    rec["bytes_raw"] = float(ca.get("bytes accessed", -1))
    rec["flops"] = a["flops_corrected"]
    rec["bytes"] = a["bytes_corrected"]
    rec["coll_bytes"] = a["collective_bytes_total"]
    rec["coll_by_op"] = a["collective_bytes"]
    ma = compiled.memory_analysis()
    if ma is not None:
        rec["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
        rec["arg_bytes"] = int(getattr(ma, "argument_size_in_bytes", 0))

    # ---- the three terms (seconds) ----
    t_comp = rec["flops"] / PEAK_FLOPS
    t_mem = rec["bytes"] / HBM_BW
    t_coll = rec["coll_bytes"] / LINK_BW
    rec["t_compute_s"] = t_comp
    rec["t_memory_s"] = t_mem
    rec["t_collective_s"] = t_coll
    rec["bottleneck"] = max(
        (("compute", t_comp), ("memory", t_mem), ("collective", t_coll)),
        key=lambda kv: kv[1])[0]

    mf = model_flops(C.get_config(arch), shape)  # exact config's 6ND
    rec["model_flops_global"] = mf
    rec["useful_flops_frac"] = mf / CHIPS / max(rec["flops"], 1.0)
    # structural MFU: time the chips *must* spend on useful math vs the
    # modeled step time (max of the three terms)
    t_star = max(t_comp, t_mem, t_coll)
    rec["roofline_frac"] = (mf / CHIPS / PEAK_FLOPS) / max(t_star, 1e-30)
    rec["wall_s"] = round(time.time() - t0, 1)

    out_dir.mkdir(parents=True, exist_ok=True)
    out_file.write_text(json.dumps(rec, indent=1))
    return rec


def fmt_row(r: dict) -> str:
    return (f"| {r['cell'].replace('__',' / '):44s} "
            f"| {r['t_compute_s']*1e3:9.2f} | {r['t_memory_s']*1e3:9.2f} "
            f"| {r['t_collective_s']*1e3:9.2f} | {r['bottleneck']:10s} "
            f"| {r['useful_flops_frac']:5.2f} | {r['roofline_frac']:6.3f} |")


HEADER = ("| cell | t_comp (ms) | t_mem (ms) | t_coll (ms) | bottleneck "
          "| useful | roofline |\n|---|---|---|---|---|---|---|")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None,
                    help="arch__shape (e.g. granite_34b__train_4k)")
    ap.add_argument("--out", default="results/roofline")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--attribute", action="store_true",
                    help="print top dot-flops + collective-bytes sources")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--tp-align", action="store_true",
                    help="lower with TP-aligned (padded) head counts")
    ap.add_argument("--tag", default="",
                    help="suffix for the result file (hillclimb variants)")
    ap.add_argument("--table", action="store_true",
                    help="print markdown table from saved results")
    args = ap.parse_args()
    from repro import compile_cache
    compile_cache.enable()
    out_dir = Path(args.out)

    from repro import configs as C

    if args.table:
        print(HEADER)
        for f in sorted(out_dir.glob("*.json")):
            r = json.loads(f.read_text())
            if "t_compute_s" in r:
                print(fmt_row(r))
        return

    cells = []
    for arch in C.ARCHS:
        for shape, skip in C.arch_shapes(arch):
            name = f"{arch}__{shape[0]}"
            if args.cell and args.cell != name:
                continue
            cells.append((arch, shape, skip))
    print(HEADER)
    for arch, shape, skip in cells:
        if skip:
            print(f"| {arch} / {shape[0]} | SKIP: {skip} |")
            continue
        cfg_override = None
        tag = args.tag
        if args.tp_align:
            from repro.models import tp_align
            cfg_override = tp_align.aligned(C.get_config(arch), tp=16)
            tag = tag or "tpalign"
        r = analyze_cell(arch, shape, out_dir, force=args.force,
                         microbatch=args.microbatch, save_hlo=args.save_hlo,
                         cfg_override=cfg_override, tag=tag)
        print(fmt_row(r), flush=True)
        if args.attribute:
            from repro.launch import dryrun as DR
            from repro.launch import hlo_analysis as H
            lowered, cfg, mesh = DR.lower_cell(arch, shape, multi_pod=False,
                                               microbatch=args.microbatch)
            with mesh:
                text = lowered.compile().as_text()
            print("  top dot flops:")
            for row in H.attribute_dots(text, 8):
                print(f"    {row['flops']:10.3g}  {row['op'][-100:]}")
            print("  top collective bytes:")
            for row in H.attribute_collectives(text, 8):
                print(f"    {row['bytes']:10.3g}  {row['op'][-100:]}")


if __name__ == "__main__":
    main()
