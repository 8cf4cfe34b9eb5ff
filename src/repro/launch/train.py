"""End-to-end PAC+ trainer CLI.

Runs the paper's full workflow (Fig. 4): quantize → init adapters →
plan → epoch-1 (backbone fwd + adapter update, cache capture) →
epoch≥2 (cache hit, adapter-only). CPU-runnable with --reduced.

The flags here are a thin veneer over :class:`repro.runtime.RunSpec` —
``main()`` is exactly flags → RunSpec → ``EdgeSession.run()``. All run
logic (device pool, plan resolution, mesh, cache wiring, the epoch
loop and its step dispatch) lives in :mod:`repro.runtime`; use that API
directly to embed a run programmatically (see docs/ARCHITECTURE.md,
"The runtime layer").

With ``--dp``/``--stages`` the trainer executes the planner's hybrid
parallelism on a real 2-D ``(dp, stage)`` device mesh (paper Fig. 10/11):
epoch-1 stages the frozen-backbone forward over the pipeline axis with
1F1B micro-batching and AllReduces the adapter grads across ``dp``; from
epoch 2 the warm activation cache drops the run to *pure* data
parallelism. On CPU the mesh is emulated with
``compat.force_host_device_count`` (dp·stages fake host devices) — the
same path CI exercises on every PR.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --epochs 3 --steps-per-epoch 8 --batch 4 --seq 32

    # hybrid DP×PP on an emulated 4-device mesh
    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --dp 2 --stages 2 --epochs 3 --batch 4 --seq 32

With ``--plan`` the planner's Plan *is* the runtime contract (paper
§V-A, Alg. 1 — the point of the system): ``--plan auto`` runs Alg. 1 at
period granularity over a ``--pool``-sized device pool, and the winning
plan selects the stage count, the (possibly uneven) per-stage layer
boundaries, and the micro-batch count; the mesh is built from the plan
and the hybrid step executes those exact boundaries (ragged stages run
padded slabs with masked identity periods). ``--plan <file.json>``
replays a plan saved earlier with ``--save-plan`` (`Plan.to_json`
round-trip).

    # plan-driven: Alg. 1 chooses stages/boundaries/micro, trainer executes it
    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --plan auto --pool 4 --epochs 3 --batch 4 --seq 32

    # save once, replay on the pool
    PYTHONPATH=src python -m repro.launch.train --reduced --plan auto \
        --save-plan plan.json && \
    PYTHONPATH=src python -m repro.launch.train --reduced --plan plan.json

With ``--cache-dir`` the activation cache persists across runs: the
first run captures (compressed per ``--cache-compress``) entries and
writes a manifest fingerprinting the backbone + corpus; a second run
against the same dir validates the manifest and performs **zero**
backbone forwards — every epoch, including the first, trains straight
from the cache. Any change to the backbone (seed, quantization), the
corpus, or the compression policy invalidates the cache loudly.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --cache-dir act_cache --cache-compress int8

With ``--kernels pallas`` the whole run leaves the dense-jnp path — the
flag selects the OpSet (`repro.core.opset`) every step dispatches
through. Epoch 1's frozen forward runs on still-quantized block params
(`quant_matmul` dequantises INT8/INT4 weights in VMEM) with Pallas flash
attention, and its taps are quantized *at the tap site* into the cache's
storage form (``--cache-compress``) — no f32 HBM round-trip before
``put_batch``. The cached (epoch≥2) step runs the fused Pallas fast path
(`repro.kernels.cached_step`): entries reach the step as int8 payload +
scales / bf16 and dequantise in VMEM, and the LM-head cross-entropy
streams over vocab blocks so the (B,S,vocab) logits are never
materialised. Off-TPU the kernels run in interpreter mode (bit-accurate,
not fast) — the default ``--kernels ref`` is the dense jnp oracle the
Pallas path is tested against.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --quant 8 --cache-compress int8 --kernels pallas
"""

from __future__ import annotations

import argparse

from repro import compat
from repro.runtime import ConsoleHook, EdgeSession, RunSpec, RunSpecError

_EPILOG = """\
Full flag reference with one runnable example per flag: docs/CLI.md.
Module→paper map and the data-flow of an epoch-1 vs cached epoch:
docs/ARCHITECTURE.md. Programmatic API (RunSpec → EdgeSession →
EpochRunner): the "runtime layer" section of docs/ARCHITECTURE.md.
"""


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true", help="CPU-scale variant")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--steps-per-epoch", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--r", type=int, default=8, help="adapter reduction factor")
    ap.add_argument("--quant", type=int, default=None, choices=[4, 8])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--init", default="pruning", choices=["pruning", "random"])
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--cache-dir", default=None,
                    help="persist the activation cache here; a later run against "
                         "the same dir resumes warm (zero backbone forwards)")
    ap.add_argument("--cache-compress", default="f32", choices=["f32", "bf16", "int8"],
                    help="activation-cache entry compression policy")
    ap.add_argument("--cache-budget-mb", type=int, default=4096,
                    help="RAM budget for cache entries (compressed bytes)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dp", type=int, default=1, help="data-parallel mesh axis")
    ap.add_argument("--stages", type=int, default=1, help="pipeline stages (mesh axis)")
    ap.add_argument("--micro", type=int, default=None,
                    help="micro-batches per minibatch (default: --stages; a "
                         "replayed plan's micro count with --plan <file>; "
                         "swept and selected by the planner with --plan auto)")
    ap.add_argument("--plan", default=None,
                    help="'auto' (run Alg. 1 and execute its winning plan: "
                         "stage count, layer boundaries, micro count) or a "
                         "plan JSON saved with --save-plan")
    ap.add_argument("--pool", type=int, default=None,
                    help="device-pool size for --plan auto (default: "
                         "max(dp*stages, 4); the mesh uses dp*stages <= pool)")
    ap.add_argument("--save-plan", default=None,
                    help="write the executed plan as JSON for later replay")
    ap.add_argument("--kernels", default="ref", choices=["ref", "pallas"],
                    help="compute path for epoch 1 AND the cached epochs: "
                         "'ref' = dense jnp oracle; 'pallas' = OpSet "
                         "dispatch to quant_matmul/flash-attention on the "
                         "epoch-1 frozen forward (taps emitted in cache "
                         "storage form) plus the fused dequant×adapter + "
                         "blockwise-CE cached step (interpret mode off-TPU)")
    args = ap.parse_args()

    # config only — the backend (and its device count) is still unset
    compat.enable_compilation_cache()
    try:
        spec = RunSpec.from_args(args)
        EdgeSession(spec, log=print).run(hooks=(ConsoleHook(),))
    except RunSpecError as e:
        raise SystemExit(str(e))


if __name__ == "__main__":
    main()
