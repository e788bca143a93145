"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, extract memory/cost/collective analyses, write JSON.

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
        --shape train_4k --mesh both

``main()`` forces 512 host devices before JAX's backend starts; importing
this module leaves ``XLA_FLAGS`` and the device count alone.
"""
import argparse
import json
import os
import pathlib
import time
import traceback

import jax

from ..configs import ARCHS, SHAPES
from ..models.model import decode_step, prefill
from ..train.step import build_train_step
from .hlo import analyze
from .mesh import (
    HBM_BW,
    ICI_BW_PER_LINK,
    PEAK_FLOPS_BF16,
    make_production_mesh,
)
from .specs import build_cell, model_flops, param_counts

FORCED_DEVICES = 512

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "dryrun_results"


# ---------------------------------------------------------------------------
# variants (perf hillclimbing levers — EXPERIMENTS.md §Perf)
# each: optional RunConfig overrides + optional activation sharding rules
# ---------------------------------------------------------------------------
STREAM = {"attn_stream_bf16": True, "ssd_stream_bf16": True}
STREAM2 = dict(STREAM, norm_stats_only_f32=True, attn_chunk_q=2048,
               attn_chunk_k=2048)
VARIANTS: dict[str, dict] = {
    "baseline": {},
    "stream_bf16": {"run": STREAM},
    "sp": {"rules": "seq"},
    "sp_stream": {"run": STREAM, "rules": "seq"},
    "ep": {"run": {"moe_impl": "ep"}},
    "ep_stream": {"run": dict(STREAM, moe_impl="ep"), "rules": None},
    "ep_sp_stream": {"run": dict(STREAM, moe_impl="ep"), "rules": "seq"},
    "remat_none": {"run": {"remat": "none"}},
    "no_zero1": {"run": {"zero1": False}},
    "chunk256": {"run": {"attn_chunk_q": 256, "attn_chunk_k": 256}},
    "chunk2k": {"run": {"attn_chunk_q": 2048, "attn_chunk_k": 2048}},
    "stream_chunk2k": {
        "run": dict(STREAM, attn_chunk_q=2048, attn_chunk_k=2048)
    },
    "ep_stream_chunk2k": {
        "run": dict(STREAM, moe_impl="ep", attn_chunk_q=2048, attn_chunk_k=2048)
    },
    "stream2": {"run": STREAM2},
    "ssd128": {"run": {"ssd_chunk": 128}},
    "ssd64": {"run": {"ssd_chunk": 64}},
    "ssd128_stream": {"run": dict(STREAM, ssd_chunk=128)},
    "ep_stream2": {"run": dict(STREAM2, moe_impl="ep")},
}


def run_cell(arch: str, shape_name: str, mesh_name: str, variant: str = "baseline"):
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    spec = VARIANTS[variant]
    cell = build_cell(cfg, shape, mesh, run_overrides=spec.get("run"))
    run = cell.run
    rules = None
    if spec.get("rules") == "seq":
        from ..dist.sharding import SEQ_RULES

        rules = SEQ_RULES

    if cell.kind == "train":
        fn = build_train_step(cfg, run)
    elif cell.kind == "prefill":
        fn = lambda params, batch: prefill(params, batch, cfg, run)
    else:
        fn = lambda params, caches, batch: decode_step(params, caches, batch, cfg, run)

    from ..shardctx import clear_ctx, set_ctx

    set_ctx(mesh, rules)
    t0 = time.monotonic()
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower
    clear_ctx()

    mem = compiled.memory_analysis()
    mem_d = {
        k: int(getattr(mem, k))
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        )
        if hasattr(mem, k)
    }
    # trip-count-aware analysis over the optimized HLO (repro/launch/hlo.py)
    # — compiled.cost_analysis() counts scan bodies once and has no
    # collective term, so it is recorded only as a cross-reference.
    hlo = analyze(compiled.as_text())
    flops = float(hlo["flops"])
    bytes_accessed = float(hlo["bytes"])
    coll = hlo["collectives"]
    xla_cost = compiled.cost_analysis() or {}

    n_chips = mesh.size
    mf = model_flops(cfg, shape, run)
    counts = param_counts(cfg, run)
    compute_term = flops / PEAK_FLOPS_BF16
    memory_term = bytes_accessed / HBM_BW
    collective_term = coll.get("total", 0.0) / ICI_BW_PER_LINK
    terms = {
        "compute_s": compute_term,
        "memory_s": memory_term,
        "collective_s": collective_term,
    }
    dominant = max(terms, key=terms.get)
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "variant": variant,
        "kind": cell.kind,
        "n_chips": n_chips,
        "kv_cache_dtype": run.kv_cache_dtype,
        "remat": run.remat,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_d,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "xla_cost_flops_unscaled": float(xla_cost.get("flops", 0.0)),
        "collectives_per_chip": coll,
        "params_total": counts["total"],
        "params_active": counts["active"],
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / flops if flops else 0.0,
        "roofline": dict(terms, dominant=dominant),
        "step_time_lower_bound_s": max(terms.values()),
    }
    return result


def main():
    # jax fixes the host device count when its backend starts, so this must
    # run before the first device query below
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={FORCED_DEVICES}"
    )
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["pod", "multipod", "both"])
    ap.add_argument("--variant", default="baseline", choices=sorted(VARIANTS))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape_name in shapes:
            if shape_name == "long_500k" and not ARCHS[arch].sub_quadratic:
                print(f"SKIP {arch} x long_500k (full attention; DESIGN.md)")
                continue
            for mesh_name in meshes:
                tag = f"{arch}__{shape_name}__{mesh_name}__{args.variant}"
                out_file = outdir / f"{tag}.json"
                if out_file.exists() and not args.force:
                    print(f"cached {tag}")
                    continue
                print(f"=== {tag}")
                try:
                    res = run_cell(arch, shape_name, mesh_name, args.variant)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((tag, str(e)))
                    continue
                out_file.write_text(json.dumps(res, indent=1))
                r = res["roofline"]
                print(
                    f"  ok: compile {res['compile_s']}s  "
                    f"flops/chip {res['hlo_flops_per_chip']:.3g}  "
                    f"terms c/m/x = {r['compute_s']:.4f}/{r['memory_s']:.4f}/"
                    f"{r['collective_s']:.4f}s  dominant={r['dominant']}  "
                    f"useful={res['useful_flops_ratio']:.2f}"
                )
                jax.clear_caches()
    if failures:
        print("FAILURES:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
