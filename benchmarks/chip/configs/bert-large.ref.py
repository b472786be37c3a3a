"""Plain reference for bert-large MLM training under LAMB.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: the encoder forward pass and the masked-LM loss,
whose gradient ``jax.grad`` takes; LAMB itself is the shared
``chipbench/ref_lamb.py``, told here which leaves are decayed and how
they are sliced.  It follows the configuration in ``bert-large.json`` as
it is run, departures included, and imports nothing of the program under
test.  ``train_flops_per_token`` is the count ``train_mfu`` divides by.

``mm`` is the one matrix-product function every einsum goes through: the
reference passes ``highest``; the precision control passes a function that
rounds both operands to float8 first.

Weights are drawn here, from the seed, for the program and for the
reference alike; the layout (names and shapes) is the benchmark's and is
checked against the program's before a run starts.
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


# ---------------------------------------------------------------------------
# layout and weights
# ---------------------------------------------------------------------------

def param_specs(cfg):
    """Nested dict of (shape, init) with init in normal | ones | zeros.
    Leaves under "blocks" carry a leading layer axis."""
    d, h, f, v, n = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["intermediate_size"], cfg["vocab_size"],
                     cfg["num_hidden_layers"])
    dh = d // h
    ln = lambda *lead: {"scale": (lead + (d,), "ones"),  # noqa: E731
                        "bias": (lead + (d,), "zeros")}
    return {
        "embed": ((v, d), "normal"),
        "blocks": {
            "ln1": ln(n), "ln2": ln(n),
            "attn": {"wq": ((n, d, h, dh), "normal"),
                     "wk": ((n, d, h, dh), "normal"),
                     "wv": ((n, d, h, dh), "normal"),
                     "wo": ((n, h, dh, d), "normal")},
            "mlp": {"wi": ((n, d, f), "normal"), "wo": ((n, f, d), "normal")},
        },
        "final_norm": ln(),
    }


def _is_spec(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], str)


def init_params(cfg, key, dtype=jnp.float32):
    """Weights from ``key``: BERT's truncated normal at
    ``initializer_range`` for every matrix, LayerNorm scale 1, bias 0;
    drawn in float32 and rounded to ``dtype``."""
    std = cfg["initializer_range"]
    flat, tree = jax.tree_util.tree_flatten(param_specs(cfg), is_leaf=_is_spec)
    out = []
    for i, (shape, kind) in enumerate(flat):
        if kind == "ones":
            out.append(jnp.ones(shape, jnp.float32))
        elif kind == "zeros":
            out.append(jnp.zeros(shape, jnp.float32))
        else:
            out.append(std * jax.random.truncated_normal(
                jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32))
    return jax.tree_util.tree_unflatten(
        tree, [x.astype(dtype) for x in out])


def decayed(path: str) -> bool:
    """Weight decay and the trust ratio apply to every matrix and the
    embedding, not to LayerNorm scales and biases (LAMB's reference code)."""
    return not (path.endswith("scale") or path.endswith("bias"))


def slice_axes(path: str) -> int:
    """Leaves under "blocks" take one trust ratio per layer."""
    return 1 if path.startswith("blocks/") else 0


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


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


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def encoder(params, tokens, cfg, mm=highest):
    """Final hidden states (B, S, D) of the bidirectional encoder."""
    eps, theta = cfg["layer_norm_eps"], cfg["rope_theta"]
    x = params["embed"][tokens]

    def layer(x, p):
        a = p["attn"]
        h = layer_norm(x, p["ln1"], eps)
        q = rope(mm("bsd,dhk->bshk", h, a["wq"]), theta)
        k = rope(mm("bsd,dhk->bshk", h, a["wk"]), theta)
        v = mm("bsd,dhk->bshk", h, a["wv"])
        scores = mm("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
        probs = jax.nn.softmax(scores, axis=-1)
        o = mm("bhqt,bthk->bqhk", probs, v)
        x = x + mm("bshk,hkd->bsd", o, a["wo"])
        h = layer_norm(x, p["ln2"], eps)
        x = x + mm("bsf,fd->bsd",
                   gelu_tanh(mm("bsd,df->bsf", h, p["mlp"]["wi"])),
                   p["mlp"]["wo"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return layer_norm(x, params["final_norm"], eps)


def nll_sum(params, tokens, labels, cfg, mm=highest):
    """The masked-LM loss: (sum of the negative log-likelihoods at labels
    >= 0, their count), the MLM head being the tied embedding."""
    hid = encoder(params, tokens, cfg, mm)
    logits = mm("bsd,vd->bsv", hid, params["embed"])
    mask = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    return jnp.sum(jnp.where(mask, lse - ll, 0.0)), jnp.sum(mask)


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------

def train_flops_per_token(cfg, seq, n_pred):
    """Model operations per token of one MLM training step: forward and
    backward (3 matrix products per forward one) of the encoder's weight
    products, attention's score and value products over ``seq`` keys, and
    the tied MLM head on the ``n_pred`` predicted rows of each sequence.
    Recomputation is not counted; a multiply-add is two operations."""
    d, n, ff, v = (cfg["hidden_size"], cfg["num_hidden_layers"],
                   cfg["intermediate_size"], cfg["vocab_size"])
    weights = n * (4 * d * d + 2 * d * ff)
    attention = n * 2 * seq * d
    head = d * v * n_pred / seq
    return 6.0 * (weights + attention + head)
