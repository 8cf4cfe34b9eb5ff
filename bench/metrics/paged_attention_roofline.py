"""Share of its roofline that ``kernels/paged_attention`` reaches: one
call per layer per decode step, its operations and bytes the
architecture's (``paged_attention_work``). Memory bound at any context
on this chip."""

from bench import manifest, trace

PATTERN = r"paged_attention"


def step_work(config, ctx):
    return manifest.arch_module(config).paged_attention_work(config["arch"], ctx)


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
