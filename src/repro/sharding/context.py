"""Ambient sharding context.

Model code annotates activations with *logical* axis names via
:func:`shard_act`.  Whether (and how) that becomes a
``with_sharding_constraint`` is decided by the ambient :class:`ShardCtx`
installed by the launcher / dry-run.  Unit tests and single-device smoke runs
simply never install a context, and every annotation is a no-op.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Callable, Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.sharding.axes import default_act_rules, resolve_spec

_state = threading.local()


class ShardCtx:
    """A mesh plus the activation rule set annotations resolve against.

    Install with :func:`use_sharding`; model code then sees it through
    :func:`shard_act`.  ``act_rules`` defaults to
    :func:`~repro.sharding.axes.default_act_rules` for the mesh's pod
    structure; :meth:`with_rules` derives a context with single-rule
    overrides (e.g. ``cache_seq=("data",)`` for long-context decode).
    """

    def __init__(self, mesh: Mesh, act_rules: Optional[Mapping] = None):
        self.mesh = mesh
        self.act_rules = dict(
            act_rules
            if act_rules is not None
            else default_act_rules(multi_pod="pod" in mesh.shape)
        )

    def with_rules(self, **overrides) -> "ShardCtx":
        """New context with the given activation rules replaced."""
        rules = dict(self.act_rules)
        rules.update(overrides)
        return ShardCtx(self.mesh, rules)


def current() -> Optional[ShardCtx]:
    """The ambient :class:`ShardCtx` of this thread, or ``None``."""
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_sharding(ctx: Optional[ShardCtx]):
    """Install ``ctx`` as the ambient sharding context for the block.

    Must wrap *tracing* (the first call of a jit'd function), not
    execution: ``shard_act`` reads the context when the constraint is
    staged out.  Passing ``None`` explicitly disables annotations inside
    the block (restoring the previous context on exit either way).
    """
    prev = current()
    _state.ctx = ctx
    try:
        yield
    finally:
        _state.ctx = prev


def shard_act(x, axes: Sequence[Optional[str]]):
    """Annotate activation ``x`` with logical axis names.

    With no ambient context this is the identity (single-device tests);
    with one, the names resolve through the context's activation rules to
    a ``with_sharding_constraint`` on the context's mesh.  ``axes`` must
    name every dimension of ``x`` (use ``None`` for replicated dims).
    """
    ctx = current()
    if ctx is None:
        return x
    if x.ndim != len(axes):
        raise ValueError(f"rank mismatch: {x.shape} vs logical axes {axes}")
    spec = resolve_spec(x.shape, axes, ctx.act_rules, ctx.mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def batch_local(fn: Callable, *batched, shared: Sequence = ()):
    """Call ``fn(*batched, *shared)`` shard by shard on the ambient mesh.

    GSPMD cannot partition a Pallas TPU kernel (a ``tpu_custom_call``), so
    a kernel call inside a multi-device step goes through ``jax.shard_map``
    with every mesh axis manual.  ``batched`` arrays are split on their
    leading (batch) dim over the context's ``batch`` axes and ``shared``
    arrays are replicated into every shard.  ``fn`` must be row-local, so
    no collective is needed inside; its outputs come back split like the
    batch (replicated when nothing is batched).  The transpose of
    ``shard_map`` sums a shared input's cotangent over the shards, so
    gradients stay global.  With no context, or a one-device mesh, this
    is a plain call.  The Pallas entry points (``flash_attention``,
    ``fused_ce``, ``lamb_update``) route themselves through it, so their
    callers need not know the rule.
    """
    ctx = current()
    if ctx is None or ctx.mesh.size == 1:
        return fn(*batched, *shared)
    row = PartitionSpec()
    if batched:
        lead = batched[0]
        axes = ("batch",) + (None,) * (lead.ndim - 1)
        spec = resolve_spec(lead.shape, axes, ctx.act_rules, ctx.mesh)
        row = PartitionSpec(*spec[:1])
    return jax.shard_map(
        fn, mesh=ctx.mesh,
        in_specs=(row,) * len(batched) + (PartitionSpec(),) * len(shared),
        out_specs=row, check_vma=False,
    )(*batched, *shared)
