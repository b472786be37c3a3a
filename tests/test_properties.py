"""Hypothesis property-based tests on the system's invariants."""
import os
import sys

import pytest

# benchmarks.* (the bench protocol invariants below; tests run PYTHONPATH=src)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)"
)
import hypothesis.extra.numpy as hnp
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np

from repro import core, optim
from repro.core.strategy import trust_ratio
from repro.sharding import resolve_spec

hypothesis.settings.register_profile(
    "repro", deadline=None, max_examples=25, derandomize=True,
    suppress_health_check=[hypothesis.HealthCheck.too_slow],
)
hypothesis.settings.load_profile("repro")

# NB: allow_subnormal=False everywhere — XLA sets flush-to-zero on the FPU,
# and hypothesis refuses to build subnormal-capable float strategies under FTZ.
finite_arrays = lambda shape: hnp.arrays(
    np.float32, shape,
    elements=st.floats(-10, 10, width=32, allow_nan=False,
                       allow_subnormal=False),
)


@hypothesis.given(
    x=finite_arrays((6, 5)),
    u=finite_arrays((6, 5)),
    c=st.floats(0.1, 100.0),
)
def test_trust_ratio_scales_linearly_with_params(x, u, c):
    """phi=id: ratio(c·x, u) == c·ratio(x, u) whenever norms are nonzero."""
    x, u = jnp.asarray(x), jnp.asarray(u)
    hypothesis.assume(float(jnp.linalg.norm(x)) > 1e-3)
    hypothesis.assume(float(jnp.linalg.norm(u)) > 1e-3)
    r1 = float(trust_ratio(x, u))
    r2 = float(trust_ratio(c * x, u))
    assert abs(r2 - c * r1) <= 1e-3 * abs(c * r1)


@hypothesis.given(
    x=finite_arrays((4, 8)),
    u=finite_arrays((4, 8)),
    lo=st.floats(0.0, 1.0),
    span=st.floats(0.1, 10.0),
)
def test_trust_ratio_respects_phi_bounds(x, u, lo, span):
    x, u = jnp.asarray(x), jnp.asarray(u)
    hypothesis.assume(float(jnp.linalg.norm(u)) > 1e-3)
    hypothesis.assume(float(jnp.linalg.norm(x)) > 1e-3)
    hi = lo + span
    r = float(trust_ratio(x, u, phi_bounds=(lo, hi)))
    un = float(jnp.linalg.norm(u))
    # relative tolerance: the ratio is computed in fp32
    assert (lo / un) * (1 - 1e-5) - 1e-6 <= r <= (hi / un) * (1 + 1e-5) + 1e-6


@hypothesis.given(
    mag=hnp.arrays(np.float32, (5, 4),
                   elements=st.floats(0.0099999997764825821, 10, width=32,
                                      allow_subnormal=False)),
    signs=hnp.arrays(np.bool_, (5, 4)),
    scale=st.floats(0.5, 200.0),
)
def test_lamb_update_invariant_to_gradient_scale(mag, signs, scale):
    """From zero moments LAMB's direction is gradient-scale invariant.

    Gradients are bounded away from zero by construction: eps=0 gives exact
    invariance but makes r = m/sqrt(v) literally 0/0 on zero coordinates
    (the production path uses eps>0)."""
    g = np.where(signs, mag, -mag).astype(np.float32)
    params = {"w": jnp.ones((5, 4))}
    opt = core.lamb(0.01, weight_decay=0.0, eps=0.0)
    u1, _ = opt.update({"w": jnp.asarray(g)}, opt.init(params), params)
    u2, _ = opt.update({"w": jnp.asarray(g * scale)}, opt.init(params), params)
    np.testing.assert_allclose(
        np.asarray(u1["w"]), np.asarray(u2["w"]), rtol=1e-3, atol=1e-5
    )


@hypothesis.given(
    steps=st.integers(2, 500),
    warmup_frac=st.floats(0.01, 0.9),
    base=st.floats(1e-5, 1.0),
)
def test_warmup_poly_schedule_bounded_and_nonnegative(steps, warmup_frac, base):
    warmup = max(int(steps * warmup_frac), 1)
    s = core.warmup_poly_decay(base, steps, warmup)
    ts = jnp.arange(0, steps + 1)
    vals = np.asarray(jax.vmap(s)(ts))
    assert np.all(vals >= -1e-9)
    assert np.all(vals <= base + 1e-9)


@hypothesis.given(
    batch=st.sampled_from([512, 1024, 4096, 16384, 65536]),
)
def test_sqrt_scaling_composition(batch):
    """Scaling 512→B equals 512→2B→B composition (consistency)."""
    a = core.sqrt_scaled_lr(1e-3, 512, batch)
    b = core.sqrt_scaled_lr(core.sqrt_scaled_lr(1e-3, 512, 2048), 2048, batch)
    assert abs(a - b) < 1e-12


# -- convergence-bench protocol invariants (pure recipe / budget math) -------

@hypothesis.given(
    tokens=st.integers(1, 10**9),
    batch=st.integers(1, 65536),
    seq=st.sampled_from([32, 128, 512]),
    k=st.integers(1, 64),
)
def test_fixed_epoch_steps_monotone_and_budget_safe(tokens, batch, seq, k):
    """Fixed-epoch budget: steps never grow with batch, never spend more
    than the token budget (except via the floor of 2), and are deterministic."""
    from benchmarks.common import fixed_epoch_steps

    s = fixed_epoch_steps(tokens, batch, seq)
    assert s == fixed_epoch_steps(tokens, batch, seq)      # deterministic
    assert s >= 2                                          # floor
    assert fixed_epoch_steps(tokens, batch * k, seq) <= s  # monotone in batch
    assert s == 2 or s * batch * seq <= tokens             # budget-safe


@hypothesis.given(
    base=st.floats(1e-5, 1.0),
    base_batch=st.sampled_from([8, 64, 512]),
    k=st.integers(1, 128),
)
def test_recipe_sqrt_lr_exact_on_squares(base, base_batch, k):
    """recipe(): at batch = base·k², the sqrt rule gives exactly k·base_lr,
    and LR is monotone non-decreasing in batch."""
    from benchmarks.protocol import recipe

    r = recipe("lamb", base_batch * k * k, base_batch=base_batch, base_lr=base)
    assert abs(r["lr"] - k * base) <= 1e-9 * k * base
    smaller = recipe("lamb", base_batch, base_batch=base_batch, base_lr=base)
    assert r["lr"] >= smaller["lr"] - 1e-12


@hypothesis.given(
    ratio=st.floats(1e-4, 1.0),
    base_batch=st.sampled_from([8, 64, 512]),
    k=st.integers(1, 4096),
)
def test_linear_epoch_warmup_ratio_bounded_and_monotone(ratio, base_batch, k):
    """Warmup fraction grows linearly with batch and saturates at 1.0 (the
    whole run) — it must stay a valid fraction at any scale."""
    r1 = core.linear_epoch_warmup_ratio(ratio, base_batch, base_batch)
    rk = core.linear_epoch_warmup_ratio(ratio, base_batch, base_batch * k)
    assert 0.0 < r1 <= 1.0 and 0.0 < rk <= 1.0
    assert rk >= r1 - 1e-12                     # monotone in batch
    if ratio * (base_batch * k) / base_batch >= 1.0:
        assert rk == 1.0                        # saturation is exact


@hypothesis.given(
    steps=st.integers(2, 400),
    warmup_frac=st.floats(0.01, 0.99),
    base=st.floats(1e-5, 1.0),
)
def test_warmup_poly_schedule_peaks_at_warmup_end(steps, warmup_frac, base):
    """The §4.1 shape the two-stage re-warm-up relies on: ramp up to the peak
    LR at ``warmup`` (monotone), then decay monotonically toward zero."""
    warmup = max(int(steps * warmup_frac), 1)
    hypothesis.assume(warmup < steps)
    s = core.warmup_poly_decay(base, steps, warmup)
    vals = np.asarray(jax.vmap(s)(jnp.arange(0, steps + 1)))
    peak = vals[warmup]
    assert abs(peak - base) <= 1e-6 * base      # peak is the base LR
    assert np.all(np.diff(vals[: warmup + 1]) >= -1e-9)   # ramp up
    assert np.all(np.diff(vals[warmup:]) <= 1e-9)         # decay down
    assert vals[-1] <= base * 1e-6 + 1e-9                 # ends ~0


@hypothesis.given(
    dims=st.lists(st.sampled_from([1, 3, 5, 15, 16, 48, 64, 960, 1024]),
                  min_size=1, max_size=4),
)
def test_resolve_spec_always_divides(dims):
    """Any resolved PartitionSpec axis product divides its dimension."""
    mesh = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    rules = {"a": ("data",), "b": ("model",), "c": ("data", "model")}
    names = ["a", "b", "c", None]
    axes = tuple(names[i % 4] for i in range(len(dims)))
    spec = resolve_spec(tuple(dims), axes, rules, mesh)
    used = []
    for dim, entry in zip(dims, tuple(spec) + (None,) * (len(dims) - len(spec))):
        if entry is None:
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for e in entries:
            assert e not in used, "mesh axis reused"
            used.append(e)
            total *= mesh.shape[e]
        assert dim % total == 0


@hypothesis.given(
    data=hnp.arrays(np.float32, (3, 7),
                    elements=st.floats(-5, 5, width=32, allow_nan=False,
                                       allow_subnormal=False)),
)
def test_apply_updates_inverse(data):
    """apply_updates(p, u) - p == u (fp32 exactness)."""
    p = {"w": jnp.asarray(data)}
    u = {"w": jnp.asarray(data * 0.5)}
    q = optim.apply_updates(p, u)
    np.testing.assert_allclose(np.asarray(q["w"] - p["w"]), np.asarray(u["w"]),
                               rtol=1e-6, atol=1e-6)


@hypothesis.given(
    seed=st.integers(0, 2**31 - 1),
    layers=st.integers(1, 4),
    per=st.sampled_from([17, 64, 300, 1024]),
)
def test_fused_lamb_kernel_matches_ref_property(seed, layers, per):
    from repro.kernels.lamb_update import lamb_update
    from repro.kernels.ref import lamb_update_ref

    rng = np.random.default_rng(seed)
    shape = (layers, per)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    m = jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.1
    v = jnp.abs(jnp.asarray(rng.standard_normal(shape), jnp.float32)) * 0.01
    kw = dict(lr=0.01, weight_decay=0.01)
    x1, m1, v1 = lamb_update(x, g, m, v, jnp.asarray(2), layer_axis=0,
                             interpret=True, **kw)[:3]
    x2, m2, v2 = lamb_update_ref(x, g, m, v, step=2, layer_axis=0, **kw)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), rtol=3e-5, atol=3e-6)
