"""Share of its roofline that ``kernels/paged_attention`` reaches: one
call per layer per decode step. For a row whose context is ``c``
tokens: 4 x n_heads x head_dim x c operations (scores and values) and
the int8 keys and values of its ``c`` tokens with their f32 scales per
KV head, plus the f32 query and output rows. Memory bound at any
context on this chip."""

from bench import trace

PATTERN = r"paged_attention"


def step_work(config, ctx):
    a = config["arch"]
    hq, hkv, hd = a["n_heads"] * a["head_dim"], a["n_kv_heads"], a["head_dim"]
    flops = sum(4 * hq * c for c in ctx)
    kv = sum(c * hkv * (2 * hd + 2 * 4) for c in ctx)
    return a["n_layers"] * flops, a["n_layers"] * (kv + len(ctx) * 2 * 4 * hq)


def read(record):
    tr, steps = record.get("trace"), record.get("engine_steps")
    if not tr or not steps:
        return None
    seconds, n = trace.kernel_time(tr, PATTERN)
    if n == 0:
        return None
    pk = record["peaks"]
    least = 0.0
    for s in steps:
        if s["decode_ctx"]:
            f, b = step_work(record["config"], s["decode_ctx"])
            least += max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / seconds, {"bound": "memory"}
