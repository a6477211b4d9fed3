"""Plain reference for serve-moonlight-16b-a3b: the decoder's full forward
pass in float32 at ``highest`` matmul precision, one layer at a time, for
one chip's share of an expert-parallel deployment.

Pre-norm decoder, x = embed[tokens]; per layer, with h = rms(x) * (1 + g):
    x += Wo . mla(h)
    x += ffn(rms(x) * (1 + g2))
logits = (rms(x) * (1 + gf)) . head (untied).

Latent attention in its expanded form, 16 heads: q = h Wq split into 128
"nope" and 64 rope dims per head (no query low rank); [c, k_r] = h Wdkv
with c the 512-wide latent, normed, and k_r one 64-wide rope key shared by
every head; k_nope = c Wuk and v = c Wuv per head.  Scores are q_nope .
k_nope + q_rope . k_r over sqrt(192), causal.  Rotary embedding on the
halves of the rope dims.

The first layer's ffn is a dense SwiGLU.  The others are experts: router
scores s = sigmoid(h Wr) over all routed experts, the top 6 of s + b
chosen (b the correction bias, which chooses and nothing else), a chosen
expert weighted s_i / (sum of the chosen s) * routed_scaling_factor.  Of the
chosen experts only those this chip holds contribute (every held expert
computes every token, weighted by a one-hot of the choice), and the two
shared experts, one SwiGLU of twice the expert width, are added once.

Routing.  ``routes`` [MoE layers, S, top_k] gives the experts a program
chose at each position (a row of -1: the reference's own top 6); the
reference then routes those experts, weighted by its own scores.  Where two
experts' biased scores lie within rounding of each other a bfloat16 program
may choose either, and the tokens after it are the model on that path, so
a comparison with the program follows the program's choice and holds the
choice itself to ``route_gaps``: how far, in the reference's own biased
scores, an expert left out outranks one chosen.

It imports nothing of the program.  It reads the weights the benchmark
drew (a dict in the program's layout: per group of layers, the layer
matrices stacked on a leading axis) and upcasts one layer at a time, so no
float32 copy of the whole model exists.  ``fp8=True`` is the control: every
matrix rounded to float8_e4m3 with one scale per output column, the rest
unchanged.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class Sizes(NamedTuple):
    eps: float
    theta: float
    heads: int
    nope: int
    rope: int
    v: int
    latent: int
    layers: int
    dense_layers: int
    routed: int  # the router's width: every routed expert of the model
    held: int  # the experts this chip holds ...
    first_held: int  # ... from this one on
    top_k: int
    scale: float
    vocab: int


def sizes(config: dict) -> Sizes:
    """The sizes the reference needs, from the configuration file."""
    if (config["scoring_func"], config["topk_method"], config["n_group"],
            config["topk_group"], config["norm_topk_prob"]) != (
                "sigmoid", "noaux_tc", 1, 1, True):
        raise ValueError("the reference routes as noaux_tc with one group and "
                         "normalised weights only")
    if config["q_lora_rank"] is not None or config["tie_word_embeddings"]:
        raise ValueError("the reference has no query low rank and an untied head")
    dep = config["deployment"]
    return Sizes(float(config["rms_norm_eps"]), float(config["rope_theta"]),
                 config["num_attention_heads"], config["qk_nope_head_dim"],
                 config["qk_rope_head_dim"], config["v_head_dim"],
                 config["kv_lora_rank"], config["num_hidden_layers"],
                 config["first_k_dense_replace"], dep["n_routed_experts_published"],
                 config["n_routed_experts"], dep["first_expert_held"],
                 config["num_experts_per_tok"], float(config["routed_scaling_factor"]),
                 config["vocab_size"])


HEAD_CHUNKS = 8  # the head is upcast a slice of the vocabulary at a time


def _w(a, fp8):
    a = a.astype(jnp.float32)
    if not fp8:
        return a
    scale = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, theta):
    """x [S, heads, d]: position i rotated by i * theta^(-2j/d), on halves."""
    s, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    c, si = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * si, b * c + a * si], -1)


def _swiglu(y, wg, wu, wd):
    return (jax.nn.silu(y @ wg) * (y @ wu)) @ wd


def _attention(y, at, cfg, fp8):
    """Causal latent attention in its expanded form, y [S, d]; returns
    [S, heads * v]."""
    s, h = y.shape[0], cfg.heads
    q = (y @ _w(at["w_q"], fp8)).reshape(s, h, cfg.nope + cfg.rope)
    q_nope, q_rope = q[..., : cfg.nope], _rope(q[..., cfg.nope:], cfg.theta)
    ckv = y @ _w(at["w_dkv"], fp8)
    c = _rms(ckv[:, : cfg.latent], at["kv_norm"].astype(jnp.float32), cfg.eps)
    k_rope = _rope(ckv[:, None, cfg.latent:], cfg.theta)[:, 0]  # one for all heads
    k_nope = (c @ _w(at["w_uk"], fp8)).reshape(s, h, cfg.nope)
    v = (c @ _w(at["w_uv"], fp8)).reshape(s, h, cfg.v)
    sc = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
          + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) / jnp.sqrt(
              jnp.float32(cfg.nope + cfg.rope))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    a = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", a, v).reshape(s, -1)


def _experts(y, scores, chosen, m, cfg, fp8):
    """The held experts' part of the routed result, plus the shared experts;
    y [S, d], scores [S, routed], chosen [S, top_k]."""
    picked = scores * jax.nn.one_hot(chosen, cfg.routed).sum(-2)
    weight = picked / picked.sum(-1, keepdims=True) * cfg.scale
    held = jax.lax.dynamic_slice_in_dim(weight, cfg.first_held, cfg.held, axis=1)
    w1, w3, w2 = (_w(m[k], fp8) for k in ("w1", "w3", "w2"))
    hid = jax.nn.silu(jnp.einsum("sd,edf->sef", y, w1)) * jnp.einsum("sd,edf->sef", y, w3)
    routed = jnp.einsum("sef,efd,se->sd", hid, w2, held)
    return routed + _swiglu(y, _w(m["ws1"], fp8), _w(m["ws3"], fp8), _w(m["ws2"], fp8))


@partial(jax.jit, static_argnames=("cfg", "fp8"))
def _layer(x, lp, i, route, cfg, fp8):
    """One layer, x [S, d]; ``route`` [S, top_k] as in ``forward``.  Returns
    x and, for an expert layer, its biased router scores [S, routed]."""
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), lp)
    y = _rms(x, lp["norm1"].astype(jnp.float32), cfg.eps)
    x = x + _attention(y, lp["attn"], cfg, fp8) @ _w(lp["attn"]["wo"], fp8)
    y = _rms(x, lp["norm2"].astype(jnp.float32), cfg.eps)
    if "mlp" in lp:
        f = lp["mlp"]
        return x + _swiglu(y, _w(f["w_gate"], fp8), _w(f["w_up"], fp8),
                           _w(f["w_down"], fp8)), None
    m = lp["moe"]
    scores = jax.nn.sigmoid(y @ _w(m["router"], fp8))
    biased = scores + m["router_bias"][:, 0]
    own = jax.lax.top_k(biased, cfg.top_k)[1]
    chosen = jnp.where(route[:, :1] >= 0, route, own)
    return x + _experts(y, scores, chosen, m, cfg, fp8), biased


@partial(jax.jit, static_argnames=("cfg", "fp8"))
def _head(x, gf, head, j, cfg, fp8):
    """One slice of the vocabulary's logits; ``head`` is [d, vocab]."""
    n = cfg.vocab // HEAD_CHUNKS
    part = jax.lax.dynamic_slice_in_dim(head, j * n, n, axis=1)
    return _rms(x, gf.astype(jnp.float32), cfg.eps) @ _w(part, fp8)


@partial(jax.jit, static_argnames=("fp8",))
def _embed(table, tokens, fp8):
    rows = table[tokens]
    if not fp8:
        return rows.astype(jnp.float32)
    return _w(rows.T, fp8).T  # one scale per embedding row


def forward(params, tokens, config: dict, fp8: bool = False, routes=None):
    """[S, vocab] float32 logits of one sequence ``tokens`` [S], and the
    expert layers' biased router scores [MoE layers, S, routed]; ``routes``
    as in the module's docstring, None for the reference's own choices."""
    cfg = sizes(config)
    if cfg.vocab % HEAD_CHUNKS:
        raise ValueError(f"vocab {cfg.vocab} not divisible by {HEAD_CHUNKS}")
    s = tokens.shape[0]
    if routes is None:
        routes = jnp.full((cfg.layers - cfg.dense_layers, s, cfg.top_k), -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens, fp8)
        dense, moe = params["groups"]
        scores = []
        for i in range(cfg.layers):
            if i < cfg.dense_layers:
                x, _ = _layer(x, dense["b0"], jnp.int32(i), routes[0], cfg, fp8)
            else:
                j = i - cfg.dense_layers
                x, biased = _layer(x, moe["b0"], jnp.int32(j), routes[j], cfg, fp8)
                scores.append(biased)
        logits = jnp.concatenate(
            [_head(x, params["final_norm"], params["head"], jnp.int32(j), cfg, fp8)
             for j in range(HEAD_CHUNKS)], axis=1)
    return logits, jnp.stack(scores)


def logits(params, tokens, config: dict, fp8: bool = False, routes=None):
    """The logits of ``forward``."""
    return forward(params, tokens, config, fp8, routes)[0]


@jax.jit
def gaps(ref_logits, chosen):
    """Per row: how far the logit of ``chosen`` lies below the best."""
    best = ref_logits.max(-1)
    return best - jnp.take_along_axis(ref_logits, chosen[:, None], -1)[:, 0]


@jax.jit
def route_gaps(scores, chosen):
    """Per row of ``scores`` [..., routed]: how far the best expert left out
    of ``chosen`` [..., top_k] outranks the worst one in it; 0 where
    ``chosen`` is the row's own top k."""
    inside = jax.nn.one_hot(chosen, scores.shape[-1]).sum(-2) > 0
    worst_in = jnp.where(inside, scores, jnp.inf).min(-1)
    best_out = jnp.where(inside, -jnp.inf, scores).max(-1)
    return jnp.maximum(best_out - worst_in, 0.0)
