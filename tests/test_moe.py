"""MoE layer: expert-parallel dispatch vs the all-experts-dense oracle,
routing (softmax, and sigmoid with a correction bias), the layer that
holds one share of the experts, and the decode entry that reads only the
chosen experts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.spans import COUNTERS
from repro.kernels.moe_decode import ops
from repro.launch.mesh import make_debug_mesh
from repro.models import moe as moe_mod
from repro.models.layers import split
from repro.parallel.sharding import make_context


def _setup(cf=8.0, dtype=jnp.float32, arch="moonshot-v1-16b-a3b"):
    cfg = get_smoke(arch)
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=cf))
    leafs = moe_mod.moe_params(jax.random.key(0), cfg)
    params, _ = split(leafs)
    params = jax.tree.map(lambda x: x.astype(dtype) if x.dtype == jnp.bfloat16 else x, params)
    if "router_bias" in params:  # a bias of the scores' own spread
        params["router_bias"] = 0.1 * jax.random.normal(
            jax.random.key(2), params["router_bias"].shape)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model), dtype) * 0.5
    return cfg, params, x


def _route(params, x, cfg):
    return moe_mod.route(params["router"], x, cfg.moe, params.get("router_bias"))


def test_dispatch_matches_dense_oracle():
    cfg, params, x = _setup()
    top_i, top_w, _ = _route(params, x, cfg)
    got = moe_mod.moe_apply(params, x, top_i, top_w, cfg, ctx=None)
    want = moe_mod.moe_dense_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_route_topk_properties():
    """Softmax routing (deepseek-v3's smoke config): the weights are the
    top-k probabilities renormalised."""
    cfg, params, x = _setup(arch="deepseek-v3-671b")
    top_i, top_w, probs = moe_mod.route(params["router"], x, cfg.moe)
    assert top_i.shape == (2, 16, cfg.moe.top_k)
    np.testing.assert_allclose(np.asarray(top_w.sum(-1)), 1.0, atol=1e-3)
    # indices are the true argmax set of the probs
    best = np.argsort(-np.asarray(probs), axis=-1)[..., : cfg.moe.top_k]
    assert set(np.asarray(top_i)[0, 0]) == set(best[0, 0])


def test_capacity_drops_under_tight_factor():
    """Under a mesh (the training layout) a tiny capacity factor (cap -> 1
    slot/expert) drops most tokens: the output departs from the dense
    oracle but stays finite, and some token rows are exactly zero (fully
    dropped)."""
    cfg, params, x = _setup(cf=1e-6)
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, num_shared=0))
    top_i, top_w, _ = _route(params, x, cfg)
    ctx = make_context(make_debug_mesh(1, 1))
    got = np.asarray(moe_mod.moe_apply(params, x, top_i, top_w, cfg, ctx=ctx))
    want = np.asarray(moe_mod.moe_dense_ref(params, x, cfg))
    assert np.isfinite(got).all()
    assert not np.allclose(got, want, atol=1e-5)  # drops happened
    row_norms = np.abs(got).reshape(-1, got.shape[-1]).max(-1)
    assert (row_norms < 1e-7).sum() > 0  # some tokens fully dropped


def test_shared_expert_added():
    cfg = get_smoke("deepseek-v3-671b")
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    leafs = moe_mod.moe_params(jax.random.key(0), cfg)
    params, _ = split(leafs)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    assert "ws1" in params  # deepseek smoke has 1 shared expert
    x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model)) * 0.5
    top_i, top_w, _ = moe_mod.route(params["router"], x, cfg.moe)
    got = moe_mod.moe_apply(params, x, top_i, top_w, cfg, ctx=None)
    want = moe_mod.moe_dense_ref(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_aux_loss_balanced_vs_collapsed():
    cfg, params, x = _setup()
    e = cfg.moe.num_experts
    # perfectly uniform router
    probs = jnp.ones((2, 16, e)) / e
    top_i = jnp.tile(jnp.arange(cfg.moe.top_k)[None, None], (2, 16, 1))
    balanced = moe_mod.aux_load_balance_loss(probs, top_i, cfg.moe)
    # collapsed: everything to expert 0
    probs_c = jnp.zeros((2, 16, e)).at[..., 0].set(1.0)
    top_c = jnp.zeros_like(top_i)
    collapsed = moe_mod.aux_load_balance_loss(probs_c, top_c, cfg.moe)
    assert float(collapsed) > float(balanced)


def test_mesh_free_dispatch_drops_nothing():
    """The mesh-free path (the served one) holds every token an expert is
    sent, whatever the capacity factor: with every token of T = 32 routed to
    expert 0 (and the same three others), it equals the dense oracle."""
    cfg, params, x = _setup(cf=1e-6)
    k = cfg.moe.top_k
    top_i = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (2, 16, k))
    top_w = jax.random.uniform(jax.random.key(3), (2, 16, k))
    got = moe_mod.moe_apply(params, x, top_i, top_w, cfg, ctx=None)
    y = [jax.nn.silu(x @ params["w1"][e]) * (x @ params["w3"][e]) @ params["w2"][e]
         for e in range(k)]
    want = sum(top_w[..., e:e + 1] * y[e] for e in range(k))
    want = want + (jax.nn.silu(x @ params["ws1"]) * (x @ params["ws3"])) @ params["ws2"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_sigmoid_bias_chooses_but_does_not_weigh():
    """noaux_tc routing: the correction bias changes which experts are
    chosen, never their weights, which are the chosen unbiased sigmoid
    scores over their sum, times the routed scale."""
    cfg, params, x = _setup()
    m = cfg.moe
    top_i, top_w, scores = _route(params, x, cfg)
    np.testing.assert_allclose(np.asarray(top_w.sum(-1)), m.routed_scale, rtol=1e-5)
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(top_i), -1)
    np.testing.assert_allclose(
        np.asarray(top_w), chosen / chosen.sum(-1, keepdims=True) * m.routed_scale,
        rtol=1e-5)
    # a bias that lifts the least-scored expert of token 0 puts it in, at
    # the weight its own score gives
    e = int(np.argmin(np.asarray(scores)[0, 0]))
    lifted = dict(params, router_bias=params["router_bias"].at[e].set(10.0))
    ti2, tw2, sc2 = _route(lifted, x, cfg)
    np.testing.assert_array_equal(np.asarray(sc2), np.asarray(scores))
    assert e not in np.asarray(top_i)[0, 0] and e in np.asarray(ti2)[0, 0]
    chosen2 = np.take_along_axis(np.asarray(scores), np.asarray(ti2), -1)
    np.testing.assert_allclose(
        np.asarray(tw2), chosen2 / chosen2.sum(-1, keepdims=True) * m.routed_scale,
        rtol=1e-5)
    # with no bias the choice is the top-k of the scores themselves
    ti0, _, _ = moe_mod.route(params["router"], x, m)
    best = np.argsort(-np.asarray(scores), axis=-1)[..., : m.top_k]
    assert (np.sort(np.asarray(ti0), -1) == np.sort(best, -1)).all()


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four EP4 shares, each holding a quarter of the experts and routing
    over all of them: their partial outputs, with the shared experts
    counted once, add up to the uncut layer."""
    cfg, params, x = _setup()
    m = cfg.moe
    held = m.num_experts // 4
    top_i, top_w, _ = _route(params, x, cfg)
    routed = cfg.with_(moe=dataclasses.replace(m, num_shared=0))
    total = moe_mod.moe_apply(params, x, top_i, top_w, cfg, ctx=None)
    parts = 0.0
    for r in range(4):
        share = routed.with_(moe=dataclasses.replace(
            routed.moe, experts_held=held, expert_offset=r * held))
        p = dict(params, **{w: params[w][r * held:(r + 1) * held]
                            for w in ("w1", "w3", "w2")})
        part = moe_mod.moe_apply(p, x, top_i, top_w, share, ctx=None)
        np.testing.assert_allclose(np.asarray(part),
                                   np.asarray(moe_mod.moe_dense_ref(p, x, share)),
                                   atol=2e-5)
        parts = parts + part
    shared = (jax.nn.silu(x @ params["ws1"]) * (x @ params["ws3"])) @ params["ws2"]
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(total), atol=2e-5)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(moe_mod.moe_dense_ref(params, x, cfg)),
                               atol=2e-5)


def test_decode_step_records_the_experts_chosen():
    """``decode_step(..., routes=True)`` gives the same logits and caches as
    the plain step, and records per expert layer the experts chosen: a
    correction bias that lifts six experts far above the rest is followed
    in every layer."""
    from repro.models import lm

    cfg = get_smoke("moonshot-v1-16b-a3b").with_(dtype="float32")
    params = lm.init(cfg, jax.random.key(0))[0]
    lifted = np.array([1, 4, 6, 9, 13, 15])[: cfg.moe.top_k]
    dense, moe_group = params["groups"]
    bias = jnp.zeros_like(moe_group["b0"]["moe"]["router_bias"]).at[:, lifted].set(10.0)
    moe_group["b0"]["moe"]["router_bias"] = bias
    caches = lm.init_caches(cfg, 1, 4)
    tok = jnp.asarray([[3]], jnp.int32)
    plain, c_plain = lm.decode_step(params, tok, caches, jnp.int32(0), cfg)
    logits, c_rec, chosen = lm.decode_step(params, tok, caches, jnp.int32(0), cfg,
                                           routes=True)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(plain))
    for a, b in zip(jax.tree.leaves(c_rec), jax.tree.leaves(c_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    (none,), (top,) = chosen
    assert none is None  # the dense first layer routes nothing
    n_moe = cfg.n_layers - cfg.moe.first_k_dense
    assert top.shape == (n_moe, 1, 1, cfg.moe.top_k)
    assert (np.sort(np.asarray(top), -1) == np.sort(lifted)).all()


def _decode_setup(offset, shared, choice, t):
    """The sigmoid-routed smoke layer holding experts [offset, offset + 8)
    of 16, top 3, with a correction bias that sends every token to held
    experts only, to none, or to some; one or two tokens."""
    cfg, params, _ = _setup()
    m = dataclasses.replace(cfg.moe, top_k=3, experts_held=8, expert_offset=offset,
                            num_shared=2 if shared else 0)
    cfg = cfg.with_(moe=m)
    params = dict(params, **{w: params[w][:8] for w in ("w1", "w3", "w2")})
    lifted = {"all": [0, 3, 6], "none": [8, 11, 13], "some": [2, 9, 14]}[choice]
    lifted = (np.array(lifted) + offset) % 16
    params["router_bias"] = params["router_bias"].at[lifted].add(10.0)
    x = jax.random.normal(jax.random.key(1), (t, 1, cfg.d_model)) * 0.5
    return cfg, params, x


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("shared", [True, False])
@pytest.mark.parametrize("choice", ["none", "some", "all"])
@pytest.mark.parametrize("offset", [0, 8])
@pytest.mark.parametrize("t", [1, 2])
def test_decode_reads_chosen_experts(t, offset, choice, shared, backend, monkeypatch):
    """Mesh-free decode with fewer slots than held experts reads only the
    chosen ones, from the stacks by layer index, or from the layer's own
    weights: it equals select-compute-scatter over every held expert and
    the dense oracle, and counts the layers it traced."""
    monkeypatch.setattr(ops, "default_backend", lambda: backend)
    cfg, params, x = _decode_setup(offset, shared, choice, t)
    m = cfg.moe
    top_i, top_w, _ = _route(params, x, cfg)
    want = moe_mod.moe_dense_ref(params, x, cfg)
    d = cfg.d_model
    scatter = moe_mod._dispatch_local(
        x.reshape(-1, d), top_i.reshape(-1, m.top_k), top_w.reshape(-1, m.top_k),
        params["w1"], params["w3"], params["w2"], m=m, lo=m.expert_offset,
        cap=None, act=jax.nn.silu).reshape(x.shape)
    if shared:
        scatter = scatter + (jax.nn.silu(x @ params["ws1"]) * (x @ params["ws3"])) @ params["ws2"]
    np.testing.assert_allclose(np.asarray(scatter), np.asarray(want), atol=2e-5)

    before = COUNTERS.snapshot()
    got = moe_mod.moe_decode(params, x, top_i, top_w, cfg)
    stacks = dict(params, **{w: jnp.stack([params[w][::-1], params[w]])
                             for w in ("w1", "w3", "w2")})
    got_stacked = moe_mod.moe_decode(stacks, x, top_i, top_w, cfg, layer=jnp.int32(1))
    after = COUNTERS.snapshot()
    for y in (got, got_stacked):
        np.testing.assert_allclose(np.asarray(y), np.asarray(scatter), atol=2e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert (after.get("moe.decode_chosen_layers", 0)
            - before.get("moe.decode_chosen_layers", 0)) == 1 + 2
    assert after.get("moe.decode_all_held_layers") == before.get("moe.decode_all_held_layers")
    held_hits = ((top_i >= offset) & (top_i < offset + 8)).sum()
    assert int(held_hits) == {"all": 3 * t, "none": 0, "some": t}[choice]


@pytest.mark.parametrize("why", ["slots", "mesh"])
def test_decode_falls_back_to_all_held(why):
    """With as many expert slots as held experts (three tokens of top 3 over
    8 held), or under a mesh, decode is ``moe_apply``, and counted so."""
    cfg, params, x = _decode_setup(0, True, "some", 3 if why == "slots" else 1)
    x = x + 0.1 * jax.random.normal(jax.random.key(5), x.shape)
    ctx = make_context(make_debug_mesh(1, 1)) if why == "mesh" else None
    top_i, top_w, _ = _route(params, x, cfg)
    before = COUNTERS.snapshot()
    got = moe_mod.moe_decode(params, x, top_i, top_w, cfg, ctx)
    after = COUNTERS.snapshot()
    want = moe_mod.moe_apply(params, x, top_i, top_w, cfg, ctx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert (after.get("moe.decode_all_held_layers", 0)
            - before.get("moe.decode_all_held_layers", 0)) == 1
    assert after.get("moe.decode_chosen_layers") == before.get("moe.decode_chosen_layers")
    assert not moe_mod.reads_chosen(cfg, ctx, x.shape[0])
