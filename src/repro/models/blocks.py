"""Residual block assembly for every architecture family.

Block kinds
-----------
  attn        GQA self-attention (+ gated MLP)        dense transformers
  local       sliding-window GQA (+ gated MLP)        recurrentgemma / hybrids
  attn_dense  attention (GQA or MLA) + dense MLP      MoE models, first-k layers
  attn_moe    attention (GQA or MLA) + MoE            MoE models
  ssm         Mamba-2 SSD mixer (no MLP)              mamba2
  rglru       RG-LRU recurrence + gated MLP           recurrentgemma
  enc         bidirectional GQA + MLP                 seamless encoder
  xdec        causal self-attn + cross-attn + MLP     seamless decoder

Every apply returns ``(x, aux_loss, cache)`` so the scan bodies in ``lm.py``
stay uniform; decode returns ``(x, cache, top_i)``, ``top_i`` the experts an
``attn_moe`` block chose and None elsewhere.  Latent attention and the
expert layer run under the name scopes ``mla`` and ``moe``, so a device
trace's op metadata can split a step between them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import ksplit, dense, mrope, param, rms_norm, rope

__all__ = [
    "block_params",
    "block_apply",
    "block_decode",
    "block_init_cache",
    "make_rope_fn",
]


# ------------------------------------------------------------------ MLP bits
def _mlp_params(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = ksplit(key, 3)
    if cfg.act == "plain":  # non-gated (seamless)
        return {
            "w_in": param(ks[0], (d, f), ("embed", "ffn")),
            "w_out": param(ks[1], (f, d), ("ffn", "embed")),
        }
    return {
        "w_gate": param(ks[0], (d, f), ("embed", "ffn")),
        "w_up": param(ks[1], (d, f), ("embed", "ffn")),
        "w_down": param(ks[2], (f, d), ("ffn", "embed")),
    }


def _mlp_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if "w_in" in p:
        return dense(jax.nn.relu(dense(x, p["w_in"])), p["w_out"])
    act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[
        cfg.act if cfg.act in ("silu", "gelu") else "silu"
    ]
    return dense(act(dense(x, p["w_gate"])) * dense(x, p["w_up"]), p["w_down"])


def make_rope_fn(cfg: ModelConfig, positions: jax.Array):
    """positions: [B,S] (standard) or [3,B,S] (M-RoPE)."""
    if cfg.mrope:
        return lambda x: mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    return lambda x: rope(x, positions, cfg.rope_theta)


def _attn_params(key, cfg: ModelConfig):
    if cfg.mla is not None:
        return attn_mod.mla_params(key, cfg)
    return attn_mod.gqa_params(key, cfg)


# -------------------------------------------------------------------- params
def block_params(key, cfg: ModelConfig, kind: str) -> dict:
    ks = ksplit(key, 4)
    norm = lambda i: param(ks[i], (cfg.d_model,), ("embed",), init="zeros")  # noqa: E731
    if kind in ("attn", "local"):
        return {
            "norm1": norm(0),
            "attn": _attn_params(ks[1], cfg),
            "norm2": norm(2),
            "mlp": _mlp_params(ks[3], cfg),
        }
    if kind == "attn_dense":
        d_ff = cfg.moe.d_ff_dense if (cfg.moe and cfg.moe.d_ff_dense) else cfg.d_ff
        return {
            "norm1": norm(0),
            "attn": _attn_params(ks[1], cfg),
            "norm2": norm(2),
            "mlp": _mlp_params(ks[3], cfg, d_ff),
        }
    if kind == "attn_moe":
        return {
            "norm1": norm(0),
            "attn": _attn_params(ks[1], cfg),
            "norm2": norm(2),
            "moe": moe_mod.moe_params(ks[3], cfg),
        }
    if kind == "ssm":
        return {"norm1": norm(0), "ssm": ssm_mod.ssm_params(ks[1], cfg)}
    if kind == "rglru":
        return {
            "norm1": norm(0),
            "rec": rglru_mod.rglru_params(ks[1], cfg),
            "norm2": norm(2),
            "mlp": _mlp_params(ks[3], cfg),
        }
    if kind == "enc":
        return {
            "norm1": norm(0),
            "attn": attn_mod.gqa_params(ks[1], cfg),
            "norm2": norm(2),
            "mlp": _mlp_params(ks[3], cfg),
        }
    if kind == "xdec":
        return {
            "norm1": norm(0),
            "attn": attn_mod.gqa_params(ks[1], cfg),
            "normx": norm(1),
            "xattn": attn_mod.gqa_params(ks[2], cfg),
            "norm2": norm(2),
            "mlp": _mlp_params(ks[3], cfg),
        }
    raise ValueError(f"unknown block kind {kind!r}")


# --------------------------------------------------------------------- apply
def _self_attn(p, x, cfg, aux, *, window=0, want_cache, bidirectional=False):
    rope_fn = make_rope_fn(cfg, aux["positions"])
    if cfg.mla is not None:
        with jax.named_scope("mla"):
            if want_cache:
                return attn_mod.mla_attend(
                    p, x, cfg, aux["positions"], chunk=aux["chunk"], return_cache=True
                )
            return attn_mod.mla_attend(p, x, cfg, aux["positions"], chunk=aux["chunk"]), None
    if bidirectional:
        q, k, v = attn_mod._qkv(p, x, cfg, rope_fn)
        o = attn_mod.flash_attention(
            q, k, v, causal=False, chunk=aux["chunk"]
        )
        y = dense(o.reshape(*x.shape[:2], -1), p["wo"])
        return (y, (k, v)) if want_cache else (y, None)
    if want_cache:
        return attn_mod.gqa_attend(
            p, x, cfg, rope_fn, window=window, chunk=aux["chunk"], return_cache=True
        )
    return (
        attn_mod.gqa_attend(p, x, cfg, rope_fn, window=window, chunk=aux["chunk"]),
        None,
    )


def _cross_attn(p, x, cfg, memory_kv):
    """Cross attention: q from x, cached (k, v) from encoder memory."""
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = dense(x, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    k, v = memory_kv
    o = attn_mod.flash_attention(q, k, v, causal=False, chunk=1024)
    return dense(o.reshape(b, s, -1), p["wo"])


def memory_kv(p_xattn, memory, cfg: ModelConfig):
    """Precompute encoder-memory K/V for one decoder layer."""
    b, s, _ = memory.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim_
    k = dense(memory, p_xattn["wk"], p_xattn.get("bk")).reshape(b, s, hkv, hd)
    v = dense(memory, p_xattn["wv"], p_xattn.get("bv")).reshape(b, s, hkv, hd)
    return k, v


def block_apply(p, x, *, kind, cfg: ModelConfig, aux, want_cache=False):
    """Returns (x, aux_loss, cache)."""
    zero = jnp.float32(0.0)
    if kind in ("attn", "attn_dense", "local", "enc"):
        window = cfg.window if kind == "local" or (kind == "attn" and cfg.window) else 0
        y, cache = _self_attn(
            p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg, aux,
            window=window, want_cache=want_cache, bidirectional=(kind == "enc"),
        )
        x = x + y
        x = x + _mlp_apply(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg)
        if want_cache and kind == "local":
            cache = _ring_from_full(cache, cfg.window)
        return x, zero, cache
    if kind == "attn_moe":
        y, cache = _self_attn(
            p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg, aux,
            want_cache=want_cache,
        )
        x = x + y
        with jax.named_scope("moe"):
            xn = rms_norm(x, p["norm2"], cfg.norm_eps)
            top_i, top_w, probs = _route(p["moe"], xn, cfg)
            aux_l = moe_mod.aux_load_balance_loss(probs, top_i, cfg.moe)
            x = x + moe_mod.moe_apply(p["moe"], xn, top_i, top_w, cfg, aux.get("ctx"))
        return x, aux_l, cache
    if kind == "ssm":
        xn = rms_norm(x, p["norm1"], cfg.norm_eps)
        if want_cache:
            y, cache = ssm_mod.ssm_apply(p["ssm"], xn, cfg, return_cache=True)
        else:
            y, cache = ssm_mod.ssm_apply(p["ssm"], xn, cfg), None
        return x + y, zero, cache
    if kind == "rglru":
        xn = rms_norm(x, p["norm1"], cfg.norm_eps)
        if want_cache:
            y, cache = rglru_mod.rglru_apply(p["rec"], xn, cfg, return_cache=True)
        else:
            y, cache = rglru_mod.rglru_apply(p["rec"], xn, cfg), None
        x = x + y
        x = x + _mlp_apply(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg)
        return x, zero, cache
    if kind == "xdec":
        y, cache = _self_attn(
            p["attn"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg, aux,
            want_cache=want_cache,
        )
        x = x + y
        mkv = aux.get("memory_kv")
        if mkv is None:
            mkv = memory_kv(p["xattn"], aux["memory"], cfg)
        x = x + _cross_attn(
            p["xattn"], rms_norm(x, p["normx"], cfg.norm_eps), cfg, mkv
        )
        x = x + _mlp_apply(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg)
        if want_cache:
            cache = (cache, mkv)
        return x, zero, cache
    raise ValueError(f"unknown block kind {kind!r}")


def _route(p_moe, x, cfg: ModelConfig):
    return moe_mod.route(p_moe["router"], x, cfg.moe, p_moe.get("router_bias"))


def _ring_from_full(kv, window):
    """Re-index the last ``window`` positions into ring-buffer slots."""
    k, v = kv
    p0 = k.shape[1]
    w = min(window, p0)
    idx = (jnp.arange(p0 - w, p0)) % window
    shape = (k.shape[0], window, *k.shape[2:])
    rk = jnp.zeros(shape, k.dtype).at[:, idx].set(k[:, -w:])
    rv = jnp.zeros(shape, v.dtype).at[:, idx].set(v[:, -w:])
    return rk, rv


# -------------------------------------------------------------------- decode
def block_decode(p, x, *, kind, cfg: ModelConfig, aux, cache, pos, layer=None):
    """Single-token step.  Returns (x, cache', top_i): the experts an
    ``attn_moe`` block chose [B, 1, top_k], None for other kinds.  ``layer``
    is passed to ``moe.moe_decode``: given, ``p``'s expert weights are the
    stacks of every layer and ``layer`` is this one's index."""
    if kind in ("attn", "attn_dense", "attn_moe", "local", "xdec"):
        xn = rms_norm(x, p["norm1"], cfg.norm_eps)
        if cfg.mla is not None:
            with jax.named_scope("mla"):
                y, cache_sa = attn_mod.mla_decode(
                    p["attn"], xn, cfg, cache if kind != "xdec" else cache[0], pos
                )
        else:
            rope_fn = make_rope_fn(cfg, aux["positions"])
            y, cache_sa = attn_mod.gqa_decode(
                p["attn"], xn, cfg, rope_fn,
                cache if kind != "xdec" else cache[0], pos,
                window=cfg.window if kind == "local" else 0,
            )
        x = x + y
        if kind == "xdec":
            mkv = cache[1]
            x = x + _cross_attn(
                p["xattn"], rms_norm(x, p["normx"], cfg.norm_eps), cfg, mkv
            )
            new_cache = (cache_sa, mkv)
        else:
            new_cache = cache_sa
        top_i = None
        if kind == "attn_moe":
            with jax.named_scope("moe"):
                xn2 = rms_norm(x, p["norm2"], cfg.norm_eps)
                top_i, top_w, _ = _route(p["moe"], xn2, cfg)
                x = x + moe_mod.moe_decode(
                    p["moe"], xn2, top_i, top_w, cfg, aux.get("ctx"), layer=layer
                )
        elif "mlp" in p:
            x = x + _mlp_apply(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg)
        return x, new_cache, top_i
    if kind == "ssm":
        y, cache = ssm_mod.ssm_decode(
            p["ssm"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg, cache
        )
        return x + y, cache, None
    if kind == "rglru":
        y, cache = rglru_mod.rglru_decode(
            p["rec"], rms_norm(x, p["norm1"], cfg.norm_eps), cfg, cache
        )
        x = x + y
        x = x + _mlp_apply(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps), cfg)
        return x, cache, None
    raise ValueError(f"unknown block kind {kind!r}")


# --------------------------------------------------------------------- cache
def block_init_cache(cfg: ModelConfig, kind: str, bsz: int, cache_len: int, dtype):
    h_kv, hd = cfg.n_kv_heads, cfg.head_dim_
    if kind in ("attn", "attn_dense", "attn_moe"):
        if cfg.mla is not None:
            m = cfg.mla
            return (
                jnp.zeros((bsz, cache_len, m.kv_lora_rank), dtype),
                jnp.zeros((bsz, cache_len, m.qk_rope_dim), dtype),
            )
        shape = (bsz, cache_len, h_kv, hd)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    if kind == "local":
        # ring buffer is ALWAYS window-sized (matches _ring_from_full and
        # stays correct when generation continues past a short prompt)
        w = cfg.window or cache_len
        shape = (bsz, w, h_kv, hd)
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    if kind == "ssm":
        return ssm_mod.ssm_init_cache(cfg, bsz, dtype)
    if kind == "rglru":
        return rglru_mod.rglru_init_cache(cfg, bsz, dtype)
    raise ValueError(f"no cache for kind {kind!r}")
