"""MoE dispatch: EP paths vs the dense oracle, capacity properties, and the
dropless held-share layer of serving against a plain float32 layer."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import registry
from repro.models import moe


def _cfg(**kw):
    base = registry.smoke("granite-moe-1b-a400m")
    return dataclasses.replace(base, **kw)


def _params(cfg, key=0):
    return moe.moe_init(jax.random.PRNGKey(key), cfg)


def test_ep_equals_oracle_on_single_rank(mesh_ctx):
    """On a 1x1 mesh the EP path must reproduce moe_apply exactly (same
    capacity discipline and slot-major priority)."""
    cfg = _cfg()
    params = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y_ref, aux_ref = moe.moe_apply(params, cfg, x)
    y_ep, aux_ep = moe.moe_apply_ep(params, cfg, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), atol=1e-6)


def test_ep_decode_equals_oracle(mesh_ctx):
    cfg = _cfg()
    params = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 1, cfg.d_model),
                          jnp.float32)
    y_ref, _ = moe.moe_apply(params, cfg, x)
    y_ep, _ = moe.moe_apply_held(params, cfg, x)
    # the held layer has no drops; the oracle may drop under capacity —
    # compare only when capacity admits everything (t=4, k=2, e=8)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_moe_capacity_drops_bounded(seed):
    """With capacity_factor >= 1, the kept fraction is at least 1/k (the
    top-1 slot of a balanced router) and never exceeds 1."""
    cfg = _cfg(capacity_factor=1.0)
    params = _params(cfg, key=seed % 7)
    x = jax.random.normal(jax.random.PRNGKey(seed), (1, 32, cfg.d_model))
    y, aux = moe.moe_apply(params, cfg, x)
    assert jnp.isfinite(y).all()
    assert float(aux) >= 0.0


def test_router_aux_penalizes_imbalance():
    """The Switch aux loss is minimized by a uniform routing distribution."""
    cfg = _cfg(router_aux_coef=1.0)
    e = cfg.n_experts
    # balanced: me = ce = uniform -> aux = coef * e * sum(1/e * 1/e) = 1
    me = jnp.full((e,), 1.0 / e)
    aux_uniform = float(e * jnp.sum(me * me))
    # imbalanced: all mass on one expert -> aux = e
    one = jnp.zeros((e,)).at[0].set(1.0)
    aux_skewed = float(e * jnp.sum(one * one))
    assert aux_skewed > aux_uniform


def test_gate_applied_at_combine(mesh_ctx):
    """Doubling the router temperature changes gates but expert inputs are
    unscaled: outputs must be a gate-weighted combination, i.e. scaling
    all gates uniformly scales the output linearly."""
    cfg = _cfg(top_k=1, capacity_factor=8.0)   # no drops
    params = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, cfg.d_model))
    y, _ = moe.moe_apply(params, cfg, x)
    # top-1 gates normalize to 1.0, so output equals the selected expert's
    # raw output; check linearity: expert(2x) != 2*expert(x) for the glu,
    # but gate*out IS linear in gate. Verify by recomputing by hand:
    t = x.reshape(-1, cfg.d_model)
    logits = t.astype(jnp.float32) @ params["router"]
    top = jnp.argmax(logits, axis=-1) - cfg.first_expert
    h = jax.nn.silu(jnp.einsum("td,edf->tef", t, params["e_gate"])) \
        * jnp.einsum("td,edf->tef", t, params["e_up"])
    y_all = jnp.einsum("tef,efd->ted", h, params["e_down"])
    held = (top >= 0) & (top < cfg.n_held_experts)
    y_hand = jnp.where(held[:, None], y_all[jnp.arange(t.shape[0]),
                                            jnp.clip(top, 0, None)], 0)
    assert bool(held.any()) and not bool(held.all())
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(y_hand), atol=1e-5, rtol=1e-5)


# ------------------------------------------- the held share, dropless


def _layer_ref(router, wg, wu, wd, first, k, x):
    """The plain float32 MoE layer at numpy's precision: softmax over
    every expert, top ``k``, renormalised, and the gated SwiGLU outputs
    of the routed experts in [first, first + len(wg)); with the routing
    counts (routings landing there, experts there touched)."""
    x, router = np.asarray(x, np.float64), np.asarray(router, np.float64)
    lg = x @ router
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1, kind="stable")[:, :k]
    g = np.take_along_axis(p, top, -1)
    g /= g.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    touched = set()
    routed = 0
    for t in range(x.shape[0]):
        for e, gate in zip(top[t], g[t]):
            j = e - first
            if 0 <= j < len(wg):
                a = x[t] @ np.asarray(wg[j], np.float64)
                u = x[t] @ np.asarray(wu[j], np.float64)
                y[t] += gate * ((a / (1 + np.exp(-a)) * u)
                                @ np.asarray(wd[j], np.float64))
                routed += 1
                touched.add(int(j))
    return y, (routed, len(touched))


def _f32(cfg, **kw):
    return dataclasses.replace(cfg, dtype="float32", **kw)


@pytest.mark.parametrize("tokens", [1, 8, 24])
def test_held_layer_matches_reference_and_counts(mesh_ctx, tokens):
    """The dropless layer adds the held experts' part of the layer, for
    every token, and counts its routings as the reference routes."""
    cfg = _f32(_cfg())
    p = _params(cfg, key=3)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens,
                                                       cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, counts = moe.moe_apply_held(p, cfg, x)
    want, want_counts = _layer_ref(p["router"], p["e_gate"], p["e_up"],
                                   p["e_down"], cfg.first_expert, cfg.top_k,
                                   x[0])
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=2e-5, rtol=1e-4)
    assert tuple(int(c) for c in counts) == want_counts
    assert want_counts[0] > 0


def test_skewed_router_drops_nothing(mesh_ctx):
    """A router biased so that one held expert takes every token: the
    held layer serves every token through it, where the capacity path
    (1.25 * t * k / e rows per expert) drops most of them."""
    cfg = _f32(_cfg(top_k=2))
    p = _params(cfg, key=5)
    hot = cfg.first_expert + 1                      # a held expert
    d = cfg.d_model
    p["router"] = p["router"].at[:, hot].set(1.0 / np.sqrt(d))
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 32, d)) + 2.0
    top = np.argmax(np.asarray(x[0] @ p["router"]), -1)
    assert (top == hot).all()
    want, (routed, _) = _layer_ref(p["router"], p["e_gate"], p["e_up"],
                                   p["e_down"], cfg.first_expert, cfg.top_k,
                                   x[0])
    with jax.default_matmul_precision("highest"):
        y, counts = moe.moe_apply_held(p, cfg, x)
        y_cap, _ = moe.moe_apply(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y[0]), want, atol=2e-5, rtol=1e-4)
    assert int(counts[0]) == routed >= 32
    # the capacity path keeps round(1.25 * 32 * 2 / 8) = 10 of 32 rows
    dropped = np.abs(np.asarray(y_cap[0]) - want).max(-1) > 1e-3
    assert dropped.sum() >= 16


def test_shares_sum_to_the_uncut_layer(mesh_ctx):
    """Sixteen chips' shares of a layer (2 of 32 experts each), added
    together, give the uncut layer; what every chip computes alike (here
    the input and the residual) is counted once."""
    n, e_loc = 16, 2
    whole = _f32(_cfg(n_experts=n * e_loc, n_experts_held=0,
                      first_expert=0, top_k=4))
    p = _params(whole, key=7)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 12, whole.d_model))
    with jax.default_matmul_precision("highest"):
        total = x
        routed = touched = 0
        for i in range(n):
            cfg = dataclasses.replace(whole, n_experts_held=e_loc,
                                      first_expert=i * e_loc)
            part = {"router": p["router"]}
            for name in ("e_gate", "e_up", "e_down"):
                part[name] = p[name][i * e_loc:(i + 1) * e_loc]
            y, counts = moe.moe_apply_held(part, cfg, x)
            total = total + y
            routed += int(counts[0])
            touched += int(counts[1])
    want, (w_routed, w_touched) = _layer_ref(
        p["router"], p["e_gate"], p["e_up"], p["e_down"], 0, whole.top_k,
        x[0])
    np.testing.assert_allclose(np.asarray(total[0]),
                               np.asarray(x[0]) + want, atol=1e-4, rtol=1e-4)
    assert (routed, touched) == (w_routed, w_touched)
    assert routed == 12 * whole.top_k


_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, numpy as np
    from jax.sharding import AxisType
    from repro.configs import registry
    from repro.models import moe
    cfg = dataclasses.replace(registry.smoke("granite-moe-1b-a400m"),
                              dtype="float32")
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, cfg.d_model))
    f = jax.jit(lambda p, x: moe.moe_apply_held(p, cfg, x))
    with jax.default_matmul_precision("highest"):
        y1, c1 = f(p, x)
        for ranks in (2, 4):
            mesh = jax.make_mesh((1, ranks), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            with jax.set_mesh(mesh):
                y2, c2 = jax.jit(lambda p, x: moe.moe_apply_held(
                    p, cfg, x))(p, x)
            np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                                       atol=1e-5, rtol=1e-5)
            assert np.asarray(c2).tolist() == np.asarray(c1).tolist()
    print("MESH_OK")
""")


def test_held_layer_on_a_mesh_matches_one_device():
    """On a (1, r) mesh each model rank holds its e/r of the held experts
    from ``first_expert + rank * e/r`` on; the psum gives the one-device
    layer and its counts."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert "MESH_OK" in res.stdout, res.stderr[-3000:]


def test_n_params_counts_the_held_experts():
    """A share's parameter count holds its experts and the whole router."""
    whole = registry.get("qwen3-moe-235b-a22b")
    share = dataclasses.replace(whole, n_layers=16, n_experts_held=8)
    cut = dataclasses.replace(whole, n_layers=16)
    per_expert = 3 * whole.d_model * whole.d_ff
    assert cut.n_params() - share.n_params() == 16 * 120 * per_expert
    assert share.n_held_experts == 8 and whole.n_held_experts == 128
