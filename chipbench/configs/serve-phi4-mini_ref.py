"""Plain reference for serve-phi4-mini: the decoder's full forward pass in
float32 at ``highest`` matmul precision, one layer at a time.

Pre-norm decoder: x = embed[tokens]; per layer
    x += Wo . attn(rope(Wq h), rope(Wk h), Wv h),  h = rms(x) * (1 + g1)
    x += Wd (silu(Wg h) * Wu h),                   h = rms(x) * (1 + g2)
logits = (rms(x) * (1 + gf)) . head, where the head is embed transposed when
the configuration ties them.  Rotary embedding over the whole head (its two
halves), causal attention with 24 query heads over 8 K/V heads
(query head j reads K/V head j // 3), scores scaled by 1/sqrt(head_dim).

It imports nothing of the program.  It reads the weights the benchmark
drew (a dict in the program's layout: the layer matrices stacked on a
leading axis) and upcasts one layer at a time, so no float32 copy of the
whole model exists.  ``fp8=True`` is the control: every matrix rounded to
float8_e4m3 with one scale per output column, the rest unchanged.
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
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    tied: bool


def sizes(config: dict) -> Sizes:
    """The sizes the reference needs, from the configuration file."""
    return Sizes(float(config["rms_norm_eps"]), float(config["rope_theta"]),
                 config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"], config["num_hidden_layers"],
                 config["vocab_size"], bool(config["tie_word_embeddings"]))


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
    s, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freq
    c, si = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * c - b * si, b * c + a * si], -1)


@partial(jax.jit, static_argnames=("cfg", "fp8"))
def _layer(x, lp, i, cfg, fp8):
    eps, h, kv, hd = cfg.eps, cfg.heads, cfg.kv_heads, cfg.head_dim
    take = lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)  # noqa: E731
    s = x.shape[0]
    y = _rms(x, take(lp["norm1"]).astype(jnp.float32), eps)
    at = lp["attn"]
    q = (y @ _w(take(at["wq"]), fp8)).reshape(s, h, hd)
    k = (y @ _w(take(at["wk"]), fp8)).reshape(s, kv, hd)
    v = (y @ _w(take(at["wv"]), fp8)).reshape(s, kv, hd)
    q, k = _rope(q, cfg.theta), _rope(k, cfg.theta)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v).reshape(s, h * hd)
    x = x + o @ _w(take(at["wo"]), fp8)
    y = _rms(x, take(lp["norm2"]).astype(jnp.float32), eps)
    m = lp["mlp"]
    g = jax.nn.silu(y @ _w(take(m["w_gate"]), fp8)) * (y @ _w(take(m["w_up"]), fp8))
    return x + g @ _w(take(m["w_down"]), fp8)


@partial(jax.jit, static_argnames=("cfg", "fp8"))
def _head(x, gf, head, j, cfg, fp8):
    """One slice of the vocabulary's logits; ``head`` is [d, vocab], or the
    [vocab, d] embedding table when the configuration ties them."""
    n = cfg.vocab // HEAD_CHUNKS
    if cfg.tied:
        part = jax.lax.dynamic_slice_in_dim(head, j * n, n, axis=0).T
    else:
        part = jax.lax.dynamic_slice_in_dim(head, j * n, n, axis=1)
    return _rms(x, gf.astype(jnp.float32), cfg.eps) @ _w(part, fp8)


@partial(jax.jit, static_argnames=("fp8",))
def _embed(table, tokens, fp8):
    rows = table[tokens]
    if not fp8:
        return rows.astype(jnp.float32)
    return _w(rows.T, fp8).T  # one scale per embedding row


def logits(params, tokens, config: dict, fp8: bool = False):
    """[S, vocab] float32 logits of one sequence ``tokens`` [S]."""
    cfg = sizes(config)
    if cfg.vocab % HEAD_CHUNKS:
        raise ValueError(f"vocab {cfg.vocab} not divisible by {HEAD_CHUNKS}")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], tokens, fp8)
        (grp,) = params["groups"]
        for i in range(cfg.layers):
            x = _layer(x, grp["b0"], jnp.int32(i), cfg, fp8)
        head = params["embed"] if cfg.tied else params["head"]
        return jnp.concatenate(
            [_head(x, params["final_norm"], head, jnp.int32(j), cfg, fp8)
             for j in range(HEAD_CHUNKS)], axis=1)


@jax.jit
def gaps(ref_logits, chosen):
    """Per row: how far the logit of ``chosen`` lies below the best."""
    best = ref_logits.max(-1)
    return best - jnp.take_along_axis(ref_logits, chosen[:, None], -1)[:, 0]
