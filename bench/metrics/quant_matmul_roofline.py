"""Share of its roofline that ``kernels/quant_matmul`` reaches in the
capture step: the least time the chip could take for every call of the
traced window — the larger of its operations over the bf16 peak and its
bytes over HBM bandwidth — over the summed device time of its events.

Calls per step, from shapes: the seven projections of each layer
(q, k, v, o; gate, up, down) at M = batch x seq rows. Bytes: the f32
activations in and out, the int8 weight and its f32 scales (one per
128 columns).
"""

from bench import trace

# the kernel's custom calls are named after the jitted function:
# %quant_matmul.49 ... %quant_matmul.55 in a TPU v5e trace of the capture step
PATTERN = r"^quant_matmul\b"
QBLOCK = 128


def calls(config, traffic):
    """(M, K, N) of every quant_matmul call of one capture step."""
    a = config["arch"]
    d, ff, hd = a["d_model"], a["d_ff"], a["head_dim"]
    hq, hkv = a["n_heads"] * hd, a["n_kv_heads"] * hd
    M = traffic["batch"] * traffic["seq"]
    layer = [(M, d, hq), (M, d, hkv), (M, d, hkv), (M, hq, d),
             (M, d, ff), (M, d, ff), (M, ff, d)]
    return layer * a["n_layers"]


def flops(M, K, N):
    return 2 * M * K * N


def bytes_moved(M, K, N, bits=8):
    return 4 * M * K + K * N * bits // 8 + 4 * K * (N // QBLOCK) + 4 * M * N


def read(record):
    tr = record.get("trace")
    if not tr or record["traffic"].get("phase") != "capture":
        return None
    seconds, n = trace.kernel_time(tr, PATTERN)
    if n == 0:
        return None
    pk = record["peaks"]
    shapes = calls(record["config"], record["traffic"])
    t_flops = sum(flops(*s) for s in shapes) / pk["bf16_flops"]
    t_bytes = sum(bytes_moved(*s) for s in shapes) / pk["hbm_bytes_per_s"]
    least = sum(max(flops(*s) / pk["bf16_flops"], bytes_moved(*s) / pk["hbm_bytes_per_s"])
                for s in shapes)
    share = 100.0 * record["steps"] * least / seconds
    return share, {"bound": "compute" if t_flops >= t_bytes else "memory"}
