"""Plain float32 reference of InternLM2 with a Parallel Adapter.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching tricks, and nothing imported from the program. It
follows the configuration file: weights made again from the seed by
``bench/weights.py`` and held at the weight precision the
configuration states (block-absmax INT8, ``weight_block`` values per
scale along the last axis, on every stacked leaf of at least
``quant_min_size`` values: the per-layer norm gains too), taps at the stated tap precision
(``tap_block``), attention, RMSNorm with a ``1 + w`` gain, rotary
embedding over the two halves of each head, GQA (query head ``h`` reads
KV head ``h // (n_heads / n_kv_heads)``), a SiLU-gated MLP, and the side
network of the PAC paper (Parallel Adapters, arXiv:2408.10746 §IV-A):

    a_0 = b_0 W_down[0];  a_i = block_i(λ_i b_i W_down[i] + (1 - λ_i) a_{i-1})
    logits = RMSNorm(b_final + RMSNorm(a_n) W_up) W_head

with λ clipped to [0, 1]. The step clips the gradient to a global norm
and applies AdamW, with the settings of the configuration's
``optimizer`` section.

The backbone runs one layer per call, so that the reference fits on the
chip beside nothing else once the program's state is freed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def qdq(x, bits: int, block: int):
    """Block-absmax quantise then dequantise along the last axis:
    ``scale = absmax / (2**(bits-1) - 1)`` per block, round to nearest."""
    qmax = 2 ** (bits - 1) - 1
    n = x.shape[-1]
    nb = -(-n // block)
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, nb * block - n)])
    xb = xp.reshape(x.shape[:-1] + (nb, block))
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / qmax
    q = jnp.clip(jnp.round(xb / jnp.where(scale > 0, scale, 1.0)), -qmax, qmax)
    return (q * scale).reshape(xp.shape)[..., :n]


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rope(x, theta):
    """x (B, S, H, hd), positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, w: dict, theta):
    B, S, _ = x.shape
    H, Hkv, hd = w["n_heads"], w["n_kv_heads"], w["head_dim"]
    q = rope(mm(x, p["wq"]).reshape(B, S, H, hd), theta)
    k = rope(mm(x, p["wk"]).reshape(B, S, Hkv, hd), theta)
    v = mm(x, p["wv"]).reshape(B, S, Hkv, hd)
    kv_of = jnp.arange(H) // (H // Hkv)
    k, v = k[:, :, kv_of], v[:, :, kv_of]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HIGHEST)
    return mm(o.reshape(B, S, H * hd), p["wo"])


def served_attention(x, p, w: dict, theta, prompt_len):
    """Attention as the paged serving path computes it: the prompt's rows
    attend to float32 keys and values (one-shot prefill), every later row
    to keys and values held at int8 per (token, KV head) in the page pool."""
    B, S, _ = x.shape
    H, Hkv, hd = w["n_heads"], w["n_kv_heads"], w["head_dim"]
    q = rope(mm(x, p["wq"]).reshape(B, S, H, hd), theta)
    k = rope(mm(x, p["wk"]).reshape(B, S, Hkv, hd), theta)
    v = mm(x, p["wv"]).reshape(B, S, Hkv, hd)
    kv_of = jnp.arange(H) // (H // Hkv)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def attend(k, v):
        k, v = k[:, :, kv_of], v[:, :, kv_of]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, precision=HIGHEST)

    decode_row = (jnp.arange(S) >= prompt_len)[None, :, None, None]
    o = jnp.where(decode_row, attend(qdq(k, 8, hd), qdq(v, 8, hd)), attend(k, v))
    return mm(o.reshape(B, S, H * hd), p["wo"])


def block(p, x, w: dict, eps, theta, prompt_len=None):
    """One decoder layer; ``p`` is flat by leaf name (weights.layer_shapes).
    With ``prompt_len``, attention is the served path's."""
    if prompt_len is None:
        mix = attention(rms_norm(x, p["ln1"], eps), p, w, theta)
    else:
        mix = served_attention(rms_norm(x, p["ln1"], eps), p, w, theta, prompt_len)
    x = x + mix
    h = rms_norm(x, p["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, p["wg"])) * mm(h, p["wi"]), p["wo_ff"])


def _flat(blk) -> dict:
    """The program's nested block layout -> flat leaf names."""
    return {"ln1": blk["ln1"], "ln2": blk["ln2"], **blk["mixer"],
            "wi": blk["ffn"]["wi"], "wg": blk["ffn"]["wg"], "wo_ff": blk["ffn"]["wo"]}


class Reference:
    """The reference model of one configuration file, from one seed."""

    def __init__(self, config: dict, seed: int):
        self.arch, self.ad = config["arch"], config["adapter"]
        self.bits = config["run"]["quant"]
        self.wblock, self.tblock = config["weight_block"], config["tap_block"]
        self.qmin = config["quant_min_size"]
        self.tap_bits = {"int8": 8, "f32": None}[config["run"]["cache_compress"]]
        self.opt = config["optimizer"]
        self.key = W.seed_key(seed, W.STREAM_BACKBONE)
        self.adapter_key = W.seed_key(seed, W.STREAM_ADAPTER)
        self.eps, self.theta = self.arch["norm_eps"], self.arch["rope_theta"]

    # -- weights at the stated precision ---------------------------------

    def wq(self, x):
        return qdq(x, self.bits, self.wblock)

    def tap(self, x):
        return x if self.tap_bits is None else qdq(x, self.tap_bits, self.tblock)

    @functools.cached_property
    def _layer(self):
        def run(key, layer, x, prompt_len=None):
            n_layers = self.arch["n_layers"]
            p = {n: self.wq(v) if n_layers * v.size >= self.qmin else v
                 for n, v in W.make_layer(key, self.arch, layer).items()}
            return block(p, x, self.arch, self.eps, self.theta, prompt_len)
        return jax.jit(run)

    @functools.cached_property
    def _embed(self):
        return jax.jit(lambda key, tokens: self.wq(W.make_embed(key, self.arch)[tokens]))

    def head(self):
        """(final_norm, dequantised lm_head)."""
        fn, head = jax.jit(lambda key: W.make_head(key, self.arch))(self.key)
        return fn, jax.jit(self.wq)(head)

    def initial_adapter(self):
        return jax.jit(lambda k: W.make_adapter(k, self.arch, self.ad))(self.adapter_key)

    # -- frozen backbone ----------------------------------------------------

    def backbone(self, tokens):
        """(b0, taps (L, B, S, d), b_final) at the stated tap precision,
        one layer per call."""
        x = self._embed(self.key, jnp.asarray(tokens))
        b0, taps = self.tap(x), []
        for layer in range(self.arch["n_layers"]):
            x = self._layer(self.key, layer, x)
            taps.append(self.tap(x))
        return b0, jnp.stack(taps), self.tap(x)

    def served_logits(self, ap, tokens, prompt_len: int, head):
        """Logits (S, vocab) of one served request: the prompt followed by
        the served tokens, through the served path's attention, float32
        taps and the request's adapter ``ap``. ``tokens`` may be padded
        at the end (causal rows before the padding are unaffected)."""
        x = self._embed(self.key, jnp.asarray(tokens)[None])
        b0, taps = x, []
        for layer in range(self.arch["n_layers"]):
            x = self._layer(self.key, layer, x, jnp.int32(prompt_len))
            taps.append(x)
        return self._served_head(ap, b0, jnp.stack(taps), x, *head)[0]

    @functools.cached_property
    def _served_head(self):
        return jax.jit(self.logits)

    # -- side network, loss and step -----------------------------------------

    def side(self, ap, b0, taps):
        eps, theta = self.eps, self.theta
        lam = jnp.clip(ap["lambda"], 0.0, 1.0)

        def period(a, xs):
            blk, down, lam_i, b_i = xs
            mixed = lam_i * mm(b_i, down) + (1.0 - lam_i) * a
            return block(_flat(blk), mixed, self.ad, eps, theta), None

        a, _ = jax.lax.scan(period, mm(b0, ap["downs"][0]),
                            (ap["blocks"][0], ap["downs"][1:], lam, taps))
        return mm(rms_norm(a, ap["out_norm"], eps), ap["up"])

    def logits(self, ap, b0, taps, bf, final_norm, head):
        h = rms_norm(bf + self.side(ap, b0, taps), final_norm, self.eps)
        return mm(h, head)

    def loss(self, ap, b0, taps, bf, labels, final_norm, head):
        logp = jax.nn.log_softmax(self.logits(ap, b0, taps, bf, final_norm, head), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))

    @functools.cached_property
    def _step(self):
        o = self.opt

        def step(ap, mu, nu, count, b0, taps, bf, labels, final_norm, head):
            loss, g = jax.value_and_grad(self.loss)(ap, b0, taps, bf, labels, final_norm, head)
            norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(1.0, o["clip"] / jnp.maximum(norm, 1e-12)), g)
            count = count + 1
            mu = jax.tree.map(lambda m, x: o["b1"] * m + (1 - o["b1"]) * x, mu, g)
            nu = jax.tree.map(lambda v, x: o["b2"] * v + (1 - o["b2"]) * x * x, nu, g)
            c1, c2 = 1 - o["b1"] ** count, 1 - o["b2"] ** count
            ap = jax.tree.map(
                lambda p, m, v: p - o["lr"] * (m / c1 / (jnp.sqrt(v / c2) + o["eps"])
                                               + o["weight_decay"] * p), ap, mu, nu)
            return loss, g, ap, mu, nu, count

        return jax.jit(step)

    def train(self, batches, start=None):
        """Follow the program's first steps from the benchmark's initial
        adapter (or ``start``). ``batches``: [(tokens, labels)], one per
        step. Returns (losses, first clipped gradient, adapter after
        the steps, adapter before them)."""
        final_norm, head = self.head()
        ap0 = self.initial_adapter() if start is None else start
        ap = ap0
        zeros = jax.tree.map(jnp.zeros_like, ap)
        mu, nu, count = zeros, zeros, jnp.zeros((), jnp.float32)
        losses, g1 = [], None
        for tokens, labels in batches:
            b0, taps, bf = self.backbone(tokens)
            loss, g, ap, mu, nu, count = self._step(
                ap, mu, nu, count, b0, taps, bf, jnp.asarray(labels), final_norm, head)
            losses.append(float(loss))
            if g1 is None:
                g1 = jax.device_get(g)
        return losses, g1, jax.device_get(ap), jax.device_get(ap0)
