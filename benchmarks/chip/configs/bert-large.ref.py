"""Plain reference for bert-large MLM training under LAMB.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: the encoder forward pass, the masked-LM loss, its
gradient by ``jax.grad``, and LAMB (You et al., ICLR 2020, Algorithm 2)
with global-norm clipping and per-layer trust ratios.  It follows the
configuration in ``bert-large.json`` as it is run, departures included,
and imports nothing of the program under test.

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


def init_params(cfg, key):
    """Float32 weights from ``key``: BERT's truncated normal at
    ``initializer_range`` for every matrix, LayerNorm scale 1, bias 0."""
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
    return jax.tree_util.tree_unflatten(tree, out)


def decayed(path: str) -> bool:
    """Weight decay and the trust ratio apply to every matrix and the
    embedding, not to LayerNorm scales and biases (LAMB's reference code)."""
    return not (path.endswith("scale") or path.endswith("bias"))


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


def mlm_nll_sum(params, tokens, labels, cfg, mm=highest):
    """(sum of the negative log-likelihoods at labels >= 0, their count),
    the MLM head being the tied embedding."""
    hid = encoder(params, tokens, cfg, mm)
    logits = mm("bsd,vd->bsv", hid, params["embed"])
    mask = labels >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    return jnp.sum(jnp.where(mask, lse - ll, 0.0)), jnp.sum(mask)


# ---------------------------------------------------------------------------
# LAMB
# ---------------------------------------------------------------------------

def slice_norms(tree):
    """{path: per-layer-slice L2 norms} (one value for unstacked leaves)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(k.key) for k in path)
        x = jnp.asarray(x, jnp.float32)
        axes = tuple(range(1, x.ndim)) if name.startswith("blocks/") else None
        out[name] = jnp.atleast_1d(jnp.sqrt(jnp.sum(x * x, axis=axes)))
    return out


def lr_at(job, count):
    """The job's schedule: linear warmup, then linear decay to 0."""
    base, total, warm = (job["learning_rate"], job["total_steps"],
                         job["warmup_steps"])
    if warm and count < warm:
        return base * count / warm
    frac = min(max((count - warm) / max(total - warm, 1), 0.0), 1.0)
    return base * (1.0 - frac)


def _lamb(params, grads, m, v, t, lr, job):
    b1, b2, eps, wd = job["b1"], job["b2"], job["eps"], job["weight_decay"]
    gsq = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
    clip = jnp.minimum(1.0, job["grad_clip_norm"] / (jnp.sqrt(gsq) + 1e-12))

    def one(path, w, g, m, v):
        name = "/".join(str(k.key) for k in path)
        g = g * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if decayed(name):
            u = u + wd * w
            axes = tuple(range(1, w.ndim)) if name.startswith("blocks/") else None
            wn = jnp.sqrt(jnp.sum(w * w, axis=axes, keepdims=axes is not None))
            un = jnp.sqrt(jnp.sum(u * u, axis=axes, keepdims=axes is not None))
            ratio = jnp.where((wn > 0) & (un > 0), wn / un, 1.0)
        else:
            ratio = 1.0
        return w - lr * ratio * u, m, v

    out = jax.tree_util.tree_map_with_path(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def lamb_steps(cfg, job, key, batches, rows, mm=highest):
    """Run LAMB from the weights of ``key`` over ``batches`` (a list of
    (tokens, labels) host arrays), ``rows`` rows at a time.

    Returns the per-step losses, the per-slice norms of the first step's
    clipped gradient, and the per-slice norms of the weights' change after
    the last step, all as numpy.
    """
    params = jax.jit(lambda k: init_params(cfg, k))(key)
    w0 = params
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: mlm_nll_sum(p, t, l, cfg, mm), has_aux=True))
    acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    update = jax.jit(lambda p, g, m, v, t, lr: _lamb(p, g, m, v, t, lr, job))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    m, v = zeros(params), zeros(params)
    losses, grad_norms = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        total = count = grads = None
        for r in range(0, tokens.shape[0], rows):
            (s, c), g = grad_fn(params, jnp.asarray(tokens[r:r + rows]),
                                jnp.asarray(labels[r:r + rows]))
            grads = g if grads is None else acc(grads, g)
            total = s if total is None else total + s
            count = c if count is None else count + c
        count = jnp.maximum(count, 1).astype(jnp.float32)
        grads = jax.tree.map(lambda g: g / count, grads)
        losses.append(float(total / count))
        if t == 1:
            gsq = sum(float(jnp.sum(g * g)) for g in jax.tree.leaves(grads))
            clip = min(1.0, job["grad_clip_norm"] / (math.sqrt(gsq) + 1e-12))
            grad_norms = {k: np.asarray(n) * clip
                          for k, n in slice_norms(grads).items()}
        params, m, v = update(params, grads, m, v, float(t),
                              lr_at(job, t - 1))
    change = jax.tree.map(jnp.subtract, params, w0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: np.asarray(n)
                             for k, n in slice_norms(change).items()}}
