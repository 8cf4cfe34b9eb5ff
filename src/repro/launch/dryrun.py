from repro.compat import force_host_device_count
force_host_device_count(512)

"""Multi-pod dry-run: lower + compile every (arch × input shape) on the
production meshes, and emit the roofline terms.

The two lines above MUST stay the first statements in this module — jax
locks the device count at first *backend initialisation*, and the 512
placeholder host devices exist only for this entry point (tests/benches
see 1).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both --out results/dryrun
"""

import argparse
import os
import json
import time
import traceback

import jax

from repro.configs import INPUT_SHAPES, get_arch, list_archs
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import analyze, format_row
from repro.launch.specs import build_case

ASSIGNED = [
    "musicgen-large",
    "grok-1-314b",
    "moonshot-v1-16b-a3b",
    "kimi-k2-1t-a32b",
    "qwen2-vl-7b",
    "xlstm-125m",
    "gemma2-2b",
    "jamba-1.5-large-398b",
    "internlm2-1.8b",
    "granite-20b",
]


def run_case(arch: str, shape_name: str, *, multi_pod: bool, technique: str,
             quant_bits=None, kv_quant=None, dtype="f32", out_dir=None, verbose=True):
    import jax.numpy as jnp

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    case = build_case(arch, shape_name, mesh, technique=technique,
                      quant_bits=quant_bits, kv_quant=kv_quant,
                      dtype={"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype])
    with jax.set_mesh(mesh):
        lowered = case.lower()
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
    n_adapter = 0
    if technique.startswith("pac"):
        from repro.core.parallel_adapters import adapter_param_count

        n_adapter = adapter_param_count(case.cfg)
    terms = analyze(
        compiled,
        arch=arch,
        shape=case.shape,
        mesh=mesh,
        technique=technique,
        note=case.note,
        n_active_params=case.cfg.active_param_count(),
        n_adapter_params=n_adapter,
    )
    rec = terms.as_dict()
    rec.update(lower_s=round(t_lower, 2), compile_s=round(t_compile, 2), status="ok")
    if verbose:
        print(format_row(terms))
        print(f"  memory_analysis: {terms.memory_analysis}")
        print(f"  lower={t_lower:.1f}s compile={t_compile:.1f}s")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'pod2' if multi_pod else 'pod1'}_{technique}"
        if quant_bits:
            tag += f"_int{quant_bits}"
        if kv_quant:
            tag += f"_kv{kv_quant}"
        if dtype != "f32":
            tag += f"_{dtype}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (default: all assigned)")
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES), help="input shape")
    ap.add_argument("--technique", default="pac",
                    choices=["pac", "pac_cached", "full", "lora"],
                    help="fine-tuning technique for train shapes")
    ap.add_argument("--quant", type=int, default=None, choices=[4, 8],
                    help="backbone quantization bits")
    ap.add_argument("--kv-quant", type=int, default=None, choices=[8],
                    help="INT8 KV cache for decode shapes (beyond-paper)")
    ap.add_argument("--dtype", default="f32", choices=["f32", "bf16"],
                    help="activation/param dtype (bf16 = TPU-native half)")
    ap.add_argument("--multi-pod", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=None, help="directory for JSON records")
    ap.add_argument("--all", action="store_true", help="run the full 10×4 matrix")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ASSIGNED
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.multi_pod]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch} × {shape} × {'2x16x16' if mp else '16x16'}"
                try:
                    run_case(arch, shape, multi_pod=mp, technique=args.technique,
                             quant_bits=args.quant, kv_quant=args.kv_quant,
                             dtype=args.dtype, out_dir=args.out)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"FAIL {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall dry-run cases compiled OK")


if __name__ == "__main__":
    main()
