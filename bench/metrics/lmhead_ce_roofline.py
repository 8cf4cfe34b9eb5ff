"""Share of its roofline that ``kernels/cached_step.lmhead_ce`` reaches:
the blockwise LM-head cross-entropy, forward and backward, once each per
training step (epoch-1 capture and cached alike).

Per step, T = batch x seq rows against the f32 (d, vocab) head: the
forward computes every logit once (2 T d V); the backward recomputes
them and forms dh (4 T d V). Bytes: the hidden rows, the head, the
labels and the per-row outputs once per call, and dh.
"""

import re

from bench import trace


def is_lmhead_ce(config):
    """The kernel's two custom calls, ``%lmhead_ce_fwd.N`` and
    ``%lmhead_ce_bwd.N`` in a TPU v5e trace: the Pallas calls that take
    the whole f32 head, (d, vocab padded up), as an operand, and are
    matched by that operand."""
    d, V = config["arch"]["d_model"], config["arch"]["vocab"]
    head = re.compile(rf"f32\[{d},(\d+)\]")

    def match(op):
        if 'custom_call_target="tpu_custom_call"' not in op:
            return False
        operands = op.split("custom-call(", 1)[-1]
        return any(V <= int(n) < V + 4096 for n in head.findall(operands))
    return match


def work(config, traffic):
    """(flops, bytes) of the forward and of the backward call, per step."""
    a = config["arch"]
    T, d, V = traffic["batch"] * traffic["seq"], a["d_model"], a["vocab"]
    head, rows = 4 * d * V, 4 * T * d
    fwd = (2 * T * d * V, rows + head + 4 * T + 8 * T)
    bwd = (4 * T * d * V, rows + head + 4 * T + 8 * T + rows)
    return [fwd, bwd]


def read(record):
    tr = record.get("trace")
    if not tr or record["traffic"]["kind"] != "train":
        return None
    seconds, n = trace.kernel_time(tr, is_lmhead_ce(record["config"]))
    if n == 0:
        return None
    pk = record["peaks"]
    calls = work(record["config"], record["traffic"])
    least = sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"]) for f, b in calls)
    bound = "compute" if all(f / pk["bf16_flops"] >= b / pk["hbm_bytes_per_s"]
                             for f, b in calls) else "memory"
    return 100.0 * record["steps"] * least / seconds, {"bound": bound}
