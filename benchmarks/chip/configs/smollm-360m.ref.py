"""Plain reference for smollm-360m: a Llama decoder in ``jax.numpy``.

Float32 with every matrix product at ``Precision.HIGHEST``: token
embedding, per layer RMSNorm, grouped-query causal attention with rotary
positions and a SwiGLU MLP, a final RMSNorm and the tied embedding as the
output head.  It follows ``smollm-360m.json`` as it is run and imports
nothing of the program under test.  ``mm`` is the one matrix-product
function every einsum goes through (the precision control swaps it).

Weights are drawn here from the seed for the program and the reference
alike.  Served, they are bfloat16: the reference takes the same bfloat16
values and computes with them in float32.  Trained, they are float32
masters, and ``nll_sum`` (the next-token loss) with ``decayed`` and
``slice_axes`` give the shared LAMB reference (``chipbench/ref_lamb.py``)
a decoder.  The operation counts at the end are what ``serve_mfu`` and
``train_mfu`` divide by.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def highest(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def param_specs(cfg):
    """Nested dict of (shape, init); leaves under "blocks" are stacked
    over layers."""
    d, h, kv, f, v, n = (cfg["hidden_size"], cfg["num_attention_heads"],
                         cfg["num_key_value_heads"], cfg["intermediate_size"],
                         cfg["vocab_size"], cfg["num_hidden_layers"])
    dh = d // h
    return {
        "embed": ((v, d), "normal"),
        "blocks": {
            "ln1": {"scale": ((n, d), "ones")},
            "ln2": {"scale": ((n, d), "ones")},
            "attn": {"wq": ((n, d, h, dh), "normal"),
                     "wk": ((n, d, kv, dh), "normal"),
                     "wv": ((n, d, kv, dh), "normal"),
                     "wo": ((n, h, dh, d), "normal")},
            "mlp": {"wi": ((n, d, f), "normal"), "wg": ((n, d, f), "normal"),
                    "wo": ((n, f, d), "normal")},
        },
        "final_norm": {"scale": ((d,), "ones")},
    }


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(cfg, key, dtype=jnp.float32):
    """Weights from ``key``: normal at ``initializer_range`` for every
    matrix (Llama's init), RMSNorm scale 1; rounded to ``dtype``."""
    std = cfg["initializer_range"]
    flat, tree = jax.tree_util.tree_flatten(param_specs(cfg), is_leaf=_is_spec)
    out = []
    for i, (shape, kind) in enumerate(flat):
        if kind == "ones":
            x = jnp.ones(shape, jnp.float32)
        else:
            x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
        out.append(x.astype(dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def decayed(path: str) -> bool:
    """Weight decay and the trust ratio apply to every matrix and the
    embedding, not to RMSNorm scales."""
    return not path.endswith("scale")


def slice_axes(path: str) -> int:
    """Leaves under "blocks" take one trust ratio per layer."""
    return 1 if path.startswith("blocks/") else 0


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotary positions, rotate-half form; x is (B, S, H, Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = np.arange(s, dtype=np.float32)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang))[None, :, None]
    sin = jnp.asarray(np.sin(ang))[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, cfg, mm=highest):
    """Next-token logits (B, S, V) in float32 for (B, S) tokens."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p32["embed"][tokens]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a = p["attn"]
        y = rms_norm(x, p["ln1"]["scale"], eps)
        q = rope(mm("bsd,dhk->bshk", y, a["wq"]), theta)
        k = rope(mm("bsd,dhk->bshk", y, a["wk"]), theta)
        v = mm("bsd,dhk->bshk", y, a["wv"])
        # query head i reads key/value head i // (h // kv)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        scores = mm("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
        scores = jnp.where(causal, scores, -jnp.inf)
        o = mm("bhqt,bthk->bqhk", jax.nn.softmax(scores, axis=-1), v)
        x = x + mm("bshk,hkd->bsd", o, a["wo"])
        y = rms_norm(x, p["ln2"]["scale"], eps)
        m = p["mlp"]
        gate = jax.nn.silu(mm("bsd,df->bsf", y, m["wg"]))
        x = x + mm("bsf,fd->bsd", gate * mm("bsd,df->bsf", y, m["wi"]), m["wo"])
        return x, None

    x, _ = jax.lax.scan(layer, x, p32["blocks"])
    x = rms_norm(x, p32["final_norm"]["scale"], eps)
    return mm("bsd,vd->bsv", x, p32["embed"])


def nll_sum(params, tokens, labels, cfg, mm=highest):
    """The next-token loss: (sum of the negative log-likelihoods at labels
    >= 0, their count), ``labels[t]`` being the token that follows
    position t."""
    lg = logits(params, tokens, cfg, mm)
    mask = labels >= 0
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    return jnp.sum(jnp.where(mask, lse - ll, 0.0)), jnp.sum(mask)


# ---------------------------------------------------------------------------
# operation counts (a multiply-add is two operations)
# ---------------------------------------------------------------------------

def decoder_layer_macs(cfg):
    """Weight multiply-adds of one token through every layer."""
    d, n, h, ff = (cfg["hidden_size"], cfg["num_hidden_layers"],
                   cfg["num_attention_heads"], cfg["intermediate_size"])
    kv = cfg.get("num_key_value_heads", h)
    dh = d // h
    return n * (2 * d * h * dh + 2 * d * kv * dh + 3 * d * ff)


def decode_token_flops(cfg, ctx):
    """Operations of one decoded token that attends to ``ctx`` positions,
    its logits included."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    attention = n * 2 * d * ctx          # scores and values, all heads
    return 2.0 * (decoder_layer_macs(cfg) + attention
                  + d * cfg["vocab_size"])


def prefill_flops(cfg, s):
    """Operations of a causal prefill of ``s`` tokens with the logits of
    its last position only."""
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    attention = n * 2 * d * s * (s + 1) / 2
    return 2.0 * (s * decoder_layer_macs(cfg) + attention
                  + d * cfg["vocab_size"])


def train_flops_per_token(cfg, seq, n_pred):
    """Model operations per token of one next-token training step:
    forward and backward (3 matrix products per forward one) of the
    layers' weight products, causal attention's score and value products
    over (seq + 1) / 2 keys on average, and the tied head on the ``n_pred``
    predicted positions of each sequence.  Recomputation is not counted."""
    d, n, v = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    attention = n * d * (seq + 1)
    head = d * v * n_pred / seq
    return 6.0 * (decoder_layer_macs(cfg) + attention + head)
