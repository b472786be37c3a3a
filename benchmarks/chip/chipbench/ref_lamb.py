"""Plain LAMB (You et al., ICLR 2020, Algorithm 2), shared by every
configuration's reference.

Float32 with every matrix product at ``Precision.HIGHEST`` (the
configuration's ``mm``), global-norm clipping, Adam's moments with bias
correction, decoupled weight decay and one trust ratio per LAMB slice.  It
imports nothing of the program under test.  What differs between
architectures comes from the configuration's reference module:

- ``init_params(cfg, key, dtype)``: the weights of ``key``;
- ``nll_sum(params, tokens, labels, cfg, mm)``: (sum of the negative
  log-likelihoods at ``labels >= 0``, their count);
- ``decayed(path)``: whether weight decay and the trust ratio apply;
- ``slice_axes(path)``: how many leading axes index the leaf's LAMB slices
  (0 for an unstacked leaf, 1 for a stack of layers, 2 for a stack of
  layers of experts that each take their own trust ratio).

A path is the leaf's keys joined by ``/`` (``blocks/attn/wq``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def path_name(path) -> str:
    return "/".join(str(k.key) for k in path)


def _axes(ndim: int, lead: int):
    """The axes a slice's norm sums over, None for the whole leaf."""
    return tuple(range(lead, ndim)) if lead else None


def slice_norms(tree, slice_axes):
    """{path: L2 norms of its LAMB slices} (one value for an unstacked
    leaf)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = path_name(path)
        x = jnp.asarray(x, jnp.float32)
        axes = _axes(x.ndim, slice_axes(name))
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


def lamb_update(params, grads, m, v, t, lr, job, decayed, slice_axes):
    """One LAMB step: (params, m, v) after it."""
    b1, b2, eps, wd = job["b1"], job["b2"], job["eps"], job["weight_decay"]
    gsq = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
    clip = jnp.minimum(1.0, job["grad_clip_norm"] / (jnp.sqrt(gsq) + 1e-12))

    def one(path, w, g, m, v):
        name = path_name(path)
        g = g * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if decayed(name):
            u = u + wd * w
            axes = _axes(w.ndim, slice_axes(name))
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


def lamb_steps(ref, cfg, job, key, batches, rows, mm):
    """Run LAMB from the weights of ``key`` over ``batches`` (a list of
    (tokens, labels) host arrays), ``rows`` rows at a time, with the
    architecture ``ref`` (a configuration's reference module).

    Returns the per-step losses, the per-slice norms of the first step's
    clipped gradient, and the per-slice norms of the weights' change after
    the last step, all as numpy.
    """
    params = jax.jit(lambda k: ref.init_params(cfg, k))(key)
    w0 = params
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: ref.nll_sum(p, t, l, cfg, mm), has_aux=True))
    acc = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    update = jax.jit(lambda p, g, m, v, t, lr: lamb_update(
        p, g, m, v, t, lr, job, ref.decayed, ref.slice_axes))
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
                          for k, n in slice_norms(grads, ref.slice_axes).items()}
        params, m, v = update(params, grads, m, v, float(t),
                              lr_at(job, t - 1))
    change = jax.tree.map(jnp.subtract, params, w0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: np.asarray(n) for k, n in
                             slice_norms(change, ref.slice_axes).items()}}
