"""Attention variants: GQA (full / sliding-window / cached) and MLA.

Train/prefill paths use a flash-style blocked softmax (``lax.scan`` over KV
chunks with running max/denominator) so the [S, S] score matrix is never
materialised — mandatory for the 32k-prefill shapes.  Decode paths attend a
query of length 1 against the cache directly.

MLA (DeepSeek-V3) implements both the *naive* expanded form (train/prefill)
and the *absorbed* form for decode, where the cache holds only the compressed
``c_kv`` (kv_lora_rank) plus the shared rope key — 576 floats/token/layer —
and the up-projections are folded into the query/output einsums.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .config import MLAConfig, ModelConfig
from .layers import ksplit, dense, param, rms_norm, rope

__all__ = [
    "gqa_params",
    "gqa_attend",
    "gqa_decode",
    "mla_params",
    "mla_attend",
    "mla_decode",
    "flash_attention",
]

_NEG = -2.0e38


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, Hkv, D]
    v: jax.Array,  # [B, Sk, Hkv, Dv]
    *,
    causal: bool,
    window: int = 0,
    q_offset: jax.Array | int = 0,
    chunk: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Blocked softmax attention (pure JAX flash).  GQA via head grouping.

    ``q_offset`` is the absolute position of q[0] (for cached prefill);
    ``window`` > 0 restricts attention to the last ``window`` keys.
    """
    b, sq, h, d = q.shape
    _, sk, hkv, dv = v.shape
    g = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # Keep Q/K/V in their storage dtype (bf16 on the target) and accumulate
    # the dots in f32 via preferred_element_type — the MXU reads bf16
    # natively, so this halves the HBM traffic of every score/PV pass vs
    # materialising f32 copies.
    qf = (q * scale).astype(q.dtype).reshape(b, sq, hkv, g, d)
    nchunk = -(-sk // chunk)
    pad = nchunk * chunk - sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = kp.reshape(b, nchunk, chunk, hkv, d)
    vc = vp.reshape(b, nchunk, chunk, hkv, dv)
    qpos = jnp.arange(sq) + q_offset  # [Sq]

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, cidx = inp
        kpos = cidx * chunk + jnp.arange(chunk)
        s = jnp.einsum(
            "bqkgd,bckd->bqkgc", qf, kb,
            preferred_element_type=jnp.float32,
        )  # [B,Sq,Hkv,G,C] f32 accum
        mask = kpos[None, :] <= qpos[:, None] if causal else (kpos[None, :] >= 0) & (qpos[:, None] >= 0)
        if window:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        mask = mask & (kpos[None, :] < sk)
        s = jnp.where(mask[None, :, None, None, :], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckv->bqkgv", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, hkv, g), _NEG, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, sq, hkv, g, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body,
        (m0, l0, a0),
        (
            jnp.moveaxis(kc, 1, 0),
            jnp.moveaxis(vc, 1, 0),
            jnp.arange(nchunk),
        ),
    )
    out = acc / jnp.maximum(l[..., None], 1e-37)
    return out.reshape(b, sq, h, dv).astype(q.dtype)


# ------------------------------------------------------------------------ GQA
def gqa_params(key, cfg: ModelConfig) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = ksplit(key, 4)
    p = {
        "wq": param(ks[0], (d, h * hd), ("embed", "heads")),
        "wk": param(ks[1], (d, hkv * hd), ("embed", "kv")),
        "wv": param(ks[2], (d, hkv * hd), ("embed", "kv")),
        "wo": param(ks[3], (h * hd, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = param(ks[0], (h * hd,), ("heads",), init="zeros")
        p["bk"] = param(ks[1], (hkv * hd,), ("kv",), init="zeros")
        p["bv"] = param(ks[2], (hkv * hd,), ("kv",), init="zeros")
    return p


def _qkv(p, x, cfg: ModelConfig, rope_fn):
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = dense(x, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    k = dense(x, p["wk"], p.get("bk")).reshape(b, s, hkv, hd)
    v = dense(x, p["wv"], p.get("bv")).reshape(b, s, hkv, hd)
    q = rope_fn(q)
    k = rope_fn(k)
    return q, k, v


def gqa_attend(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    rope_fn,
    *,
    window: int = 0,
    chunk: int = 1024,
    return_cache: bool = False,
):
    """Full/windowed causal self-attention for train & prefill."""
    q, k, v = _qkv(p, x, cfg, rope_fn)
    o = flash_attention(q, k, v, causal=True, window=window, chunk=chunk)
    y = dense(o.reshape(*x.shape[:2], -1), p["wo"])
    if return_cache:
        return y, (k, v)
    return y


def gqa_decode(
    p: dict,
    x: jax.Array,  # [B, 1, d]
    cfg: ModelConfig,
    rope_fn,
    cache: tuple[jax.Array, jax.Array],  # k/v [B, S_cache, Hkv, hd]
    pos: jax.Array,  # scalar int — number of tokens already in cache
    *,
    window: int = 0,
):
    """Single-token decode.  ``window``>0 => ring-buffer cache of that size."""
    b = x.shape[0]
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = dense(x, p["wq"], p.get("bq")).reshape(b, 1, h, hd)
    k = dense(x, p["wk"], p.get("bk")).reshape(b, 1, hkv, hd)
    v = dense(x, p["wv"], p.get("bv")).reshape(b, 1, hkv, hd)
    q = rope_fn(q)
    k = rope_fn(k)
    ck, cv = cache
    s_cache = ck.shape[1]
    slot = pos % s_cache if window else pos
    ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, slot, 0, 0))
    kpos = jnp.arange(s_cache)
    if window:
        # ring buffer: entry at slot j holds absolute position
        # pos - ((slot - j) mod S_cache)
        age = jnp.mod(slot - kpos, s_cache)
        abs_pos = pos - age
        valid = (abs_pos >= 0) & (abs_pos > pos - window)
    else:
        valid = kpos <= pos
    g = h // hkv
    qf = (q * (1.0 / math.sqrt(hd))).astype(ck.dtype).reshape(b, 1, hkv, g, hd)
    s = jnp.einsum("bqkgd,bckd->bqkgc", qf, ck,
                   preferred_element_type=jnp.float32)
    s = jnp.where(valid[None, None, None, None, :], s, _NEG)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgc,bckv->bqkgv", a.astype(cv.dtype), cv,
                   preferred_element_type=jnp.float32)
    y = dense(o.reshape(b, 1, h * hd).astype(x.dtype), p["wo"])
    return y, (ck, cv)


# ------------------------------------------------------------------------ MLA
def mla_params(key, cfg: ModelConfig) -> dict:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    ks = ksplit(key, 8)
    if m.q_lora_rank is None:
        q = {"w_q": param(ks[0], (d, h * qk), ("embed", "heads"))}
    else:
        q = {
            "w_dq": param(ks[0], (d, m.q_lora_rank), ("embed", "lora")),
            "q_norm": param(ks[1], (m.q_lora_rank,), ("lora",), init="zeros"),
            "w_uq": param(ks[2], (m.q_lora_rank, h * qk), ("lora", "heads")),
        }
    return {
        **q,
        "w_dkv": param(
            ks[3], (d, m.kv_lora_rank + m.qk_rope_dim), ("embed", "lora")
        ),
        "kv_norm": param(ks[4], (m.kv_lora_rank,), ("lora",), init="zeros"),
        "w_uk": param(
            ks[5], (m.kv_lora_rank, h * m.qk_nope_dim), ("lora", "heads")
        ),
        "w_uv": param(ks[6], (m.kv_lora_rank, h * m.v_dim), ("lora", "heads")),
        "wo": param(ks[7], (h * m.v_dim, d), ("heads", "embed")),
    }


def _mla_q(p, x, cfg, positions):
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    if "w_q" in p:
        q = dense(x, p["w_q"])
    else:
        q = dense(rms_norm(dense(x, p["w_dq"]), p["q_norm"], cfg.norm_eps),
                  p["w_uq"])
    q = q.reshape(b, s, h, qk)
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg, positions):
    m: MLAConfig = cfg.mla
    ckv = dense(x, p["w_dkv"])
    c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank :]
    c = rms_norm(c, p["kv_norm"], cfg.norm_eps)
    k_rope = rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c, k_rope  # [B,S,kvr], [B,S,rope_d]


def mla_attend(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    chunk: int = 1024,
    return_cache: bool = False,
):
    """Naive (expanded) MLA for train/prefill."""
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _mla_q(p, x, cfg, positions)
    c, k_rope = _mla_ckv(p, x, cfg, positions)
    k_nope = dense(c, p["w_uk"]).reshape(b, s, h, m.qk_nope_dim)
    v = dense(c, p["w_uv"]).reshape(b, s, h, m.v_dim)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.qk_rope_dim))],
        -1,
    )
    o = flash_attention(q, k, v, causal=True, chunk=chunk)
    y = dense(o.reshape(b, s, -1), p["wo"])
    if return_cache:
        return y, (c, k_rope)
    return y


def mla_decode(
    p: dict,
    x: jax.Array,  # [B, 1, d]
    cfg: ModelConfig,
    cache: tuple[jax.Array, jax.Array],  # c [B,S,kvr], k_rope [B,S,rope_d]
    pos: jax.Array,
):
    """Absorbed-matrix MLA decode against the compressed cache."""
    m: MLAConfig = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    positions = jnp.broadcast_to(pos, (b, 1))
    q_nope, q_rope = _mla_q(p, x, cfg, positions)  # [B,1,H,*]
    c_new, kr_new = _mla_ckv(p, x, cfg, positions)
    cc, ckr = cache
    cc = jax.lax.dynamic_update_slice(cc, c_new.astype(cc.dtype), (0, pos, 0))
    ckr = jax.lax.dynamic_update_slice(ckr, kr_new.astype(ckr.dtype), (0, pos, 0))
    # Absorb W_uk into q: q_eff[b,h,r] = q_nope . W_uk[., h, .].  All dots
    # read the compressed cache / up-projections in their storage dtype and
    # accumulate f32 (bf16 is MXU-native; f32 casts would double the cache
    # read traffic — decode is memory-bound on exactly these reads).
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_dim)
    q_eff = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk,
                       preferred_element_type=jnp.float32)  # [B,1,H,kvr]
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    s = (
        jnp.einsum("bqhr,bsr->bqhs", q_eff.astype(cc.dtype), cc,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bqhd,bsd->bqhs", q_rope.astype(ckr.dtype), ckr,
                     preferred_element_type=jnp.float32)
    ) * scale
    valid = jnp.arange(cc.shape[1]) <= pos
    s = jnp.where(valid[None, None, None, :], s, _NEG)
    a = jax.nn.softmax(s, axis=-1)
    o_c = jnp.einsum("bqhs,bsr->bqhr", a.astype(cc.dtype), cc,
                     preferred_element_type=jnp.float32)  # [B,1,H,kvr]
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_dim)
    o = jnp.einsum("bqhr,rhv->bqhv", o_c.astype(w_uv.dtype), w_uv,
                   preferred_element_type=jnp.float32)
    y = dense(o.reshape(b, 1, h * m.v_dim).astype(x.dtype), p["wo"])
    return y, (cc, ckr)
