"""Decode/prefill vs full-forward consistency — the serving correctness
contract, per architecture family (GQA, MLA+MoE, M-RoPE, local+RG-LRU, SSD,
enc-dec)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import lm

S = 24  # full sequence length for the comparisons


def _f32_cfg(arch):
    cfg = get_smoke(arch).with_(dtype="float32")
    if cfg.moe is not None:
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
    return cfg


def _f32_params(cfg):
    params, _ = lm.init(cfg, jax.random.key(0))
    return jax.tree.map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        params,
    )


def _token_batch(cfg, b=2, s=S):
    toks = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}


FAMILIES = [
    "mistral-nemo-12b",       # dense GQA
    "qwen1.5-32b",            # MHA + qkv bias
    "deepseek-v3-671b",       # MLA + MoE (+MTP params unused at serve)
    "moonshot-v1-16b-a3b",    # MLA (no q low rank) + sigmoid MoE
    "recurrentgemma-2b",      # RG-LRU + local attention cycle
    "mamba2-2.7b",            # SSD
]


@pytest.mark.parametrize("arch", FAMILIES)
def test_decode_matches_forward(arch):
    """Feeding tokens one-by-one through decode_step reproduces the full
    forward logits at every position."""
    cfg = _f32_cfg(arch)
    if cfg.ssm is not None:
        cfg = cfg.with_(ssm=dataclasses.replace(cfg.ssm, chunk=8))
    params = _f32_params(cfg)
    batch = _token_batch(cfg)
    full_logits, _ = lm.forward(params, batch, cfg)

    caches = lm.init_caches(cfg, 2, S, dtype=jnp.float32)
    step = jax.jit(lambda p, t, c, i: lm.decode_step(p, t, c, i, cfg))
    outs = []
    for i in range(S):
        logits, caches = step(params, batch["tokens"][:, i : i + 1], caches,
                              jnp.int32(i))
        outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full_logits), atol=2e-3, rtol=2e-3
    )


@pytest.mark.parametrize("arch", ["mistral-nemo-12b", "deepseek-v3-671b",
                                  "recurrentgemma-2b", "mamba2-2.7b"])
def test_prefill_matches_forward_last(arch):
    cfg = _f32_cfg(arch)
    if cfg.ssm is not None:
        cfg = cfg.with_(ssm=dataclasses.replace(cfg.ssm, chunk=8))
    params = _f32_params(cfg)
    batch = _token_batch(cfg)
    full_logits, _ = lm.forward(params, batch, cfg)
    pre_logits, _ = lm.prefill(params, batch, cfg)
    np.testing.assert_allclose(
        np.asarray(pre_logits[:, 0]), np.asarray(full_logits[:, -1]),
        atol=2e-3, rtol=2e-3,
    )


@pytest.mark.parametrize("arch", ["mistral-nemo-12b", "recurrentgemma-2b",
                                  "mamba2-2.7b", "deepseek-v3-671b"])
def test_prefill_then_decode_continues(arch):
    """prefill(prompt) + decode(rest) == forward(full) on the suffix."""
    cfg = _f32_cfg(arch)
    if cfg.ssm is not None:
        cfg = cfg.with_(ssm=dataclasses.replace(cfg.ssm, chunk=8))
    params = _f32_params(cfg)
    batch = _token_batch(cfg)
    s0 = 16
    full_logits, _ = lm.forward(params, batch, cfg)
    _, caches = lm.prefill(
        params, {"tokens": batch["tokens"][:, :s0]}, cfg
    )
    caches = lm.pad_caches(caches, cfg, S)
    outs = []
    for i in range(s0, S):
        logits, caches = lm.decode_step(
            params, batch["tokens"][:, i : i + 1], caches, jnp.int32(i), cfg
        )
        outs.append(logits)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(full_logits[:, s0:]),
        atol=2e-3, rtol=2e-3,
    )


def test_encdec_prefill_matches_forward():
    cfg = _f32_cfg("seamless-m4t-medium")
    params = _f32_params(cfg)
    b = 2
    toks = jax.random.randint(jax.random.key(1), (b, S), 0, cfg.vocab)
    enc = jax.random.normal(jax.random.key(2), (b, S, cfg.d_model)) * 0.2
    batch = {"tokens": toks, "enc_embeds": enc}
    full_logits, _ = lm.forward(
        params, {**batch, "labels": toks}, cfg
    )
    pre_logits, caches = lm.prefill(params, batch, cfg)
    np.testing.assert_allclose(
        np.asarray(pre_logits[:, 0]), np.asarray(full_logits[:, -1]),
        atol=2e-3, rtol=2e-3,
    )
    # one decode step continues coherently (cross-attn memory kv reused)
    caches = lm.pad_caches(caches, cfg, S + 4)
    nxt = jnp.argmax(pre_logits[:, -1], -1).astype(jnp.int32)[:, None]
    logits, _ = lm.decode_step(params, nxt, caches, jnp.int32(S), cfg)
    assert bool(jnp.isfinite(logits).all())


def test_vlm_forward_with_embeds():
    cfg = _f32_cfg("qwen2-vl-2b")
    params = _f32_params(cfg)
    b, s = 2, 16
    embeds = jax.random.normal(jax.random.key(3), (b, s, cfg.d_model)) * 0.2
    pos = jnp.broadcast_to(jnp.arange(s)[None, None], (3, b, s)).astype(jnp.int32)
    logits, _ = lm.forward(
        params,
        {"embeds": embeds, "positions": pos, "labels": jnp.zeros((b, s), jnp.int32)},
        cfg,
    )
    assert logits.shape == (b, s, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits[..., : cfg.vocab]).all())
