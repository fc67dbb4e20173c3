import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture × input-shape × mesh) cell and extract the roofline terms.

MUST be run as a module entry point (``python -m repro.launch.dryrun``) so the
XLA_FLAGS above land before jax initialises. Results land in
``experiments/dryrun/<arch>__<shape>__<mesh>.json`` and feed
``benchmarks/roofline.py`` and EXPERIMENTS.md §Dry-run/§Roofline.

Hardware model (TPU v5e): 197 TFLOP/s bf16/chip, 819 GB/s HBM, ~50 GB/s/link
ICI. Collective bytes are parsed from the post-SPMD optimised HLO
(``compiled.as_text()``) — cost_analysis does not report them.
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback
from pathlib import Path

import numpy as np

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link (per direction)
ICI_LINKS = 4              # v5e: 4 active ICI links usable per chip (2D torus x2 dirs)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[8,128,256]{...}' -> byte count. Tuples handled by the caller."""
    m = re.match(r"(\w+)\[([\d,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes_from_hlo(hlo: str) -> dict:
    """Sum operand bytes of every collective op in optimised HLO, by kind.

    Matches lines like:
      %ag = bf16[2,512]{1,0} all-gather(%x), replica_groups=...
      ROOT %ar = (f32[...], f32[...]) all-reduce(...)
    Operand sizes are taken from the op RESULT shape (for all-gather the
    result is the gathered size — an upper bound on moved bytes; for
    reduce-scatter the result is the scattered shard — we use the operand
    instead via the declared input shapes when present).
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo.splitlines():
        line = line.strip()
        m = re.match(
            r"(?:ROOT )?%?[\w.\-]+ = (\([^)]*\)|\S+) (all-gather|all-reduce|"
            r"reduce-scatter|all-to-all|collective-permute)", line)
        if not m:
            continue
        shapes, kind = m.groups()
        if shapes.startswith("("):
            total = sum(_shape_bytes(s.strip()) for s in shapes[1:-1].split(","))
        else:
            total = _shape_bytes(shapes)
        out[kind] += total
        counts[kind] += 1
    out["counts"] = counts
    return out


def roofline_terms(flops: float, hbm_bytes: float, coll: dict, chips: int) -> dict:
    """All inputs are PER-DEVICE quantities: XLA's cost_analysis and the
    optimised HLO text both describe the partitioned (per-chip) program, so
    each term divides by a single chip's peak — `chips` is kept only for
    bookkeeping (totals = per-device × chips under SPMD)."""
    coll_bytes = sum(v for k, v in coll.items() if k in _COLLECTIVES)
    t_compute = flops / PEAK_FLOPS
    t_memory = hbm_bytes / HBM_BW
    t_collective = coll_bytes / (ICI_BW * ICI_LINKS)
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_collective),
        key=lambda kv: kv[1],
    )[0]
    return dict(
        t_compute_s=t_compute, t_memory_s=t_memory, t_collective_s=t_collective,
        collective_bytes=coll_bytes, dominant=dominant,
    )


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE); D = tokens processed.

    For decode steps D = global_batch (one token each). Uses the UNPADDED
    config so head/vocab padding shows up as useful-ratio loss. The embedding
    table is excluded (a gather does no matmul FLOPs; the lm_head matmul is
    counted via its own weights unless tied)."""
    n = cfg.active_param_count()
    if not cfg.tie_embeddings:
        n -= cfg.vocab_size * cfg.d_model
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens  # forward only
    return 2.0 * n * shape.global_batch  # decode: 1 token per sequence


def _cost_triple(compiled) -> tuple[float, float, dict]:
    """(flops, hbm_bytes, collective-bytes-by-kind) of one executable.

    NOTE: XLA's cost_analysis visits each ``while`` body ONCE — a layer scan
    of L layers reports ~1/L of the true FLOPs. run_cell therefore derives
    per-layer costs from UNROLLED 1-layer vs 2-layer compiles (the delta is
    exactly one layer, as measured by the compiler itself) and extrapolates;
    the full scanned compile is kept for memory analysis + the pass gate.
    """
    cost = compiled.cost_analysis()
    coll = collective_bytes_from_hlo(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _depth_probe_points(cfg) -> tuple[int, int, int]:
    """(L1, L2, n_units): unrolled probe depths + how many delta-units the
    full model holds. Hybrids probe one/two periods; enc-dec scale together."""
    if cfg.family == "hybrid" and cfg.hybrid_period:
        p = cfg.hybrid_period
        return p, 2 * p, cfg.num_layers // p
    return 1, 2, cfg.num_layers


def layer_delta_costs(cfg, mesh, shape, *, ep: bool = False, **step_kw) -> dict:
    """Extrapolated whole-model costs from 1-unit vs 2-unit unrolled compiles."""
    import dataclasses as dc

    from repro.distribution.steps import make_step_for_cell

    L1, L2, n_units = _depth_probe_points(cfg)

    def probe(n_layers):
        over = dict(num_layers=n_layers, scan_layers=False)
        if cfg.encoder_layers:
            over["encoder_layers"] = n_layers
        c = dc.replace(cfg, **over)
        bundle = make_step_for_cell(c, mesh, shape, ep=ep, **step_kw)
        return _cost_triple(bundle.lower().compile())

    f1, b1, c1 = probe(L1)
    f2, b2, c2 = probe(L2)
    scale = n_units - 1
    flops = f1 + scale * (f2 - f1)
    hbm = b1 + scale * (b2 - b1)
    coll = {k: c1[k] + scale * (c2[k] - c1[k]) for k in _COLLECTIVES}
    coll["counts"] = {k: c1["counts"][k] + scale * (c2["counts"][k] - c1["counts"][k])
                      for k in _COLLECTIVES}
    if cfg.encoder_layers and cfg.encoder_layers != cfg.num_layers:
        # enc/dec probed together at equal depth; correct by the true ratio
        pass  # all assigned enc-dec archs have enc == dec depth (whisper 32/32)
    return dict(flops=flops, hbm_bytes=hbm, collectives=coll,
                probe=dict(L1=L1, L2=L2, n_units=n_units,
                           f1=f1, f2=f2, b1=b1, b2=b2))


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             *, ep: bool = False, accum: int = 1, save: bool = True,
             roofline: bool = True, overrides: dict | None = None,
             fsdp: bool = True) -> dict:
    import dataclasses as dc

    import jax
    from repro import configs
    from repro.distribution.steps import make_step_for_cell
    from repro.launch.mesh import make_production_mesh

    cfg = configs.get(arch)
    if overrides:  # perf-iteration knobs (attn_chunk, remat, dtype, ...)
        cfg = dc.replace(cfg, **overrides)
    shape = configs.SHAPES[shape_name]
    ok, why = configs.shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec = dict(arch=arch, shape=shape_name, mesh=mesh_name, status="skip", why=why)
    if not ok:
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    kw = dict(ep=ep) if ep else {}
    if accum > 1:
        kw["accum_steps"] = accum
    if not fsdp and shape.kind != "train":  # TP-only inference sharding
        kw["fsdp"] = False
    with mesh:
        # full-depth scanned compile: the dry-run gate + memory analysis
        bundle = make_step_for_cell(cfg, mesh, shape, **kw)
        lowered = bundle.lower()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        if roofline:
            # roofline terms from unrolled depth probes (see _cost_triple)
            probe_kw = {} if fsdp or shape.kind == "train" else {"fsdp": False}
            delta = layer_delta_costs(cfg, mesh, shape, ep=ep, **probe_kw)
        else:
            f, b, c = _cost_triple(compiled)
            delta = dict(flops=f, hbm_bytes=b, collectives=c, probe=None)
    dt = time.time() - t0

    # NOTE: the depth probes compile WITHOUT grad accumulation (the microbatch
    # scan would hide per-layer costs the same way the layer scan does); an
    # accum step does the same total per-layer work, so no rescaling applies —
    # accumulation changes PEAK memory (from the full compile), not traffic.
    coll = delta["collectives"]
    flops = delta["flops"]                          # per device
    hbm_bytes = delta["hbm_bytes"]                  # per device
    terms = roofline_terms(flops, hbm_bytes, coll, chips)
    mflops = model_flops(cfg, shape)                # whole-step model flops
    peak_step = max(terms["t_compute_s"], terms["t_memory_s"], terms["t_collective_s"])
    rec.update(
        status="ok",
        chips=chips,
        compile_s=round(dt, 1),
        flops=flops,
        hbm_bytes=hbm_bytes,
        model_flops=mflops,
        useful_ratio=(mflops / (flops * chips)) if flops else 0.0,
        mfu_bound=mflops / (chips * PEAK_FLOPS) / peak_step if peak_step else 0.0,
        bytes_per_device={
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "peak": mem.peak_heap_size_in_bytes
            if hasattr(mem, "peak_heap_size_in_bytes") else
            mem.argument_size_in_bytes + mem.temp_size_in_bytes,
        },
        collectives=coll,
        probe=delta.get("probe"),
        **terms,
    )
    if save:
        out_dir.mkdir(parents=True, exist_ok=True)
        tag = "__".join((configs.canonical(arch), shape_name, mesh_name))
        (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=2))
    return rec


def main(argv=None):
    from repro import configs

    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--ep", action="store_true", help="expert-parallel MoE layout")
    ap.add_argument("--accum", type=int, default=1, help="grad-accum microbatches")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    archs = list(configs.ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(configs.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    out_dir = Path(args.out)

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
                try:
                    # roofline probes are single-pod only (§Roofline); the
                    # multi-pod pass proves the "pod" axis shards + fits.
                    rec = run_cell(arch, shape, mp, out_dir,
                                   ep=args.ep, accum=args.accum,
                                   roofline=not mp)
                except Exception as e:  # a dry-run failure is a bug in the system
                    n_fail += 1
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
                    continue
                if rec["status"] == "skip":
                    n_skip += 1
                    print(f"[skip] {tag}: {rec['why']}", flush=True)
                else:
                    n_ok += 1
                    print(
                        f"[ ok ] {tag}: compile {rec['compile_s']}s  "
                        f"flops {rec['flops']:.3g}  "
                        f"t_comp {rec['t_compute_s']*1e3:.2f}ms  "
                        f"t_mem {rec['t_memory_s']*1e3:.2f}ms  "
                        f"t_coll {rec['t_collective_s']*1e3:.2f}ms  "
                        f"dom={rec['dominant']}  useful={rec['useful_ratio']:.2f}",
                        flush=True,
                    )
    print(f"\ndry-run: {n_ok} ok, {n_skip} skip, {n_fail} FAIL", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
