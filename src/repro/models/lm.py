"""Model assembly: decoder-only LMs (+ encoder-decoder) with scan-over-layers.

The layer stack is grouped into runs of identical block kinds (see
``ModelConfig.scan_groups``); each run is one ``lax.scan`` over stacked
parameters, keeping the HLO size O(1) in depth — essential for compiling the
61-layer/671B dry-run cells in reasonable time.  Rematerialisation is applied
per scan body according to ``cfg.remat``.

Public entry points (all pure functions over (params, batch)):
  init(cfg, key)            -> (params, logical_specs)
  forward(params, batch)    -> logits [B, S, vocab] (f32)
  loss_fn(params, batch)    -> (scalar loss, metrics)
  prefill(params, batch)    -> (last-token logits, caches)
  decode_step(params, tok, caches, pos) -> (logits, caches)
  decode_step(..., routes=True) -> (logits, caches, routes)
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain

from . import blocks as blk
from . import moe as moe_mod
from .config import ModelConfig
from .layers import Leaf, ksplit, param, rms_norm, softcap, split

__all__ = [
    "init",
    "init_shapes",
    "forward",
    "loss_fn",
    "prefill",
    "decode_step",
    "init_caches",
    "param_count",
]


def _group_kinds(group_kind: str) -> list[str]:
    if group_kind.startswith("cycle:"):
        return group_kind[len("cycle:") :].split("|")
    return [group_kind]


def _group_params(key, cfg: ModelConfig, group_kind: str, count: int):
    kinds = _group_kinds(group_kind)
    is_leaf = lambda x: isinstance(x, Leaf)  # noqa: E731

    def one(k):
        ks = ksplit(k, len(kinds))
        return {
            f"b{i}": blk.block_params(ks[i], cfg, kind)
            for i, kind in enumerate(kinds)
        }

    proto = one(None)
    if key is None:  # abstract: prepend the layer dim structurally
        def stack_abs(l: Leaf) -> Leaf:
            v = jax.ShapeDtypeStruct((count, *l.value.shape), l.value.dtype)
            return Leaf(v, ("layers", *l.axes))

        return jax.tree.map(stack_abs, proto, is_leaf=is_leaf)

    # Concrete: vmap one layer's init over the layer keys, so the stacked
    # arrays are drawn in place (no per-layer copies to stack afterwards,
    # and under jit one program per group rather than one per layer).
    vals = jax.vmap(lambda k: split(one(k))[0])(jax.random.split(key, count))
    return jax.tree.map(
        lambda l, v: Leaf(v, ("layers", *l.axes)), proto, vals, is_leaf=is_leaf
    )


def _decoder_groups(cfg: ModelConfig):
    if cfg.enc_layers:
        return (("xdec", cfg.n_layers),)
    return cfg.scan_groups()


def _embed_scale(cfg: ModelConfig) -> float:
    return float(cfg.d_model) ** 0.5 if cfg.family == "hybrid" else 1.0


def init(cfg: ModelConfig, key) -> tuple[Any, Any]:
    """Returns (params, logical_axes) trees (same structure).

    ``key=None`` builds the tree abstractly (ShapeDtypeStruct leaves, nothing
    allocated) — the dry-run path for 671B-scale configs.
    """
    ks = ksplit(key, 8)
    tree: dict[str, Any] = {}
    tree["embed"] = param(
        ks[0], (cfg.vocab_padded, cfg.d_model), ("vocab", "embed"), scale=0.02
    )
    groups = _decoder_groups(cfg)
    gkeys = ksplit(ks[1], len(groups))
    tree["groups"] = [
        _group_params(k, cfg, kind, count)
        for k, (kind, count) in zip(gkeys, groups)
    ]
    tree["final_norm"] = param(ks[2], (cfg.d_model,), ("embed",), init="zeros")
    if not cfg.tie_embeddings:
        tree["head"] = param(
            ks[3], (cfg.d_model, cfg.vocab_padded), ("embed", "vocab"), scale=0.02
        )
    if cfg.enc_layers:
        tree["enc_groups"] = [_group_params(ks[4], cfg, "enc", cfg.enc_layers)]
        tree["enc_norm"] = param(ks[5], (cfg.d_model,), ("embed",), init="zeros")
    if cfg.mtp:  # DeepSeek-V3 multi-token prediction module (depth 1)
        mtp_kind = cfg.block_types()[-1]
        mks = ksplit(ks[6], 4)
        tree["mtp"] = {
            "norm_h": param(mks[0], (cfg.d_model,), ("embed",), init="zeros"),
            "norm_e": param(mks[1], (cfg.d_model,), ("embed",), init="zeros"),
            "proj": param(mks[2], (2 * cfg.d_model, cfg.d_model), (None, "embed")),
            "block": blk.block_params(mks[3], cfg, mtp_kind),
        }
    return split(tree)


def init_shapes(cfg: ModelConfig) -> tuple[Any, Any]:
    """(ShapeDtypeStruct tree, logical-axes tree) — used by the dry-run."""
    shapes, specs = init(cfg, None)

    def to_sds(v):
        if isinstance(v, jax.ShapeDtypeStruct):
            return v
        return jax.ShapeDtypeStruct(v.shape, v.dtype)

    return jax.tree.map(to_sds, shapes), specs


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)


def _run_groups(params_groups, x, cfg: ModelConfig, aux, groups, want_cache=False):
    """Apply every scan group; returns (x, aux_loss_sum, caches|None)."""
    aux_total = jnp.float32(0.0)
    caches = []
    for gp, (kind, count) in zip(params_groups, groups):
        kinds = _group_kinds(kind)

        def body(carry, layer_p):
            h = constrain(carry, aux.get("ctx"), ("dp", None, None))
            a_sum = jnp.float32(0.0)
            cs = []
            for i, k in enumerate(kinds):
                h, a, c = blk.block_apply(
                    layer_p[f"b{i}"], h, kind=k, cfg=cfg, aux=aux,
                    want_cache=want_cache,
                )
                a_sum = a_sum + a
                cs.append(c)
            out = tuple(cs) if want_cache else None
            return h, (a_sum, out)

        body = _remat(body, cfg)
        x, (a_per_layer, cache_stack) = jax.lax.scan(body, x, gp)
        aux_total = aux_total + a_per_layer.sum()
        caches.append(cache_stack)
    return x, aux_total, (caches if want_cache else None)


def _embed_tokens(params, tokens, cfg: ModelConfig):
    x = params["embed"][tokens] * _embed_scale(cfg)
    return x.astype(jnp.dtype(cfg.dtype))


def _logits(params, x, cfg: ModelConfig, ctx=None):
    x = constrain(x, ctx, ("dp", None, None))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    logits = constrain(logits, ctx, ("dp", None, "tp"))
    logits = softcap(logits.astype(jnp.float32), cfg.logits_softcap)
    if cfg.vocab_padded != cfg.vocab:  # mask the padded vocab columns
        keep = jnp.arange(cfg.vocab_padded) < cfg.vocab
        logits = jnp.where(keep, logits, -2.0e38)
    return logits


def _encode(params, batch, cfg: ModelConfig, aux):
    """Encoder stack for enc-dec models (bidirectional)."""
    x = batch["enc_embeds"].astype(jnp.dtype(cfg.dtype))
    enc_aux = dict(aux)
    enc_aux["positions"] = batch.get(
        "enc_positions",
        jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2]),
    )
    x = constrain(x, aux.get("ctx"), ("dp", None, None))
    x, _, _ = _run_groups(
        params["enc_groups"], x, cfg, enc_aux, (("enc", cfg.enc_layers),)
    )
    return constrain(rms_norm(x, params["enc_norm"], cfg.norm_eps),
                     aux.get("ctx"), ("dp", None, None))


def _make_aux(batch, cfg: ModelConfig, ctx, chunk=1024):
    if cfg.mrope:
        positions = batch["positions"]  # [3, B, S]
    else:
        tokens = batch.get("tokens")
        ref = tokens if tokens is not None else batch["embeds"][..., 0]
        positions = batch.get(
            "positions",
            jnp.broadcast_to(jnp.arange(ref.shape[1])[None], ref.shape[:2]),
        )
    return {"positions": positions, "ctx": ctx, "chunk": chunk, "memory": None}


def forward(params, batch, cfg: ModelConfig, ctx=None, chunk: int = 1024):
    """Training forward.  batch: tokens [B,S] (or embeds), positions, labels."""
    aux = _make_aux(batch, cfg, ctx, chunk)
    if cfg.enc_layers:
        aux["memory"] = _encode(params, batch, cfg, aux)
    if "embeds" in batch and not cfg.enc_layers:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = _embed_tokens(params, batch["tokens"], cfg)
    x = constrain(x, ctx, ("dp", None, None))
    x, aux_loss, _ = _run_groups(params["groups"], x, cfg, aux, _decoder_groups(cfg))
    return _logits(params, x, cfg, ctx), aux_loss


def _mtp_trunk(params, h, batch, cfg: ModelConfig, aux):
    """DeepSeek-V3 MTP (depth 1): predict token t+2 from (h_t, emb_{t+1}).

    ``h`` is the trunk output BEFORE the final norm, [B, S, d].  Returns the
    MTP hidden states [B, S-1, d] (logits via the shared streamed CE head).
    """
    p = params["mtp"]
    emb = _embed_tokens(params, batch["tokens"], cfg)  # [B,S,d]
    hh = rms_norm(h[:, :-1], p["norm_h"], cfg.norm_eps)
    ee = rms_norm(emb[:, 1:], p["norm_e"], cfg.norm_eps)
    x = jnp.concatenate([hh, ee], axis=-1) @ p["proj"].astype(hh.dtype)
    x = constrain(x, aux.get("ctx"), ("dp", None, None))
    aux_m = dict(aux)
    aux_m["positions"] = aux["positions"][..., :-1]
    kind = cfg.block_types()[-1]
    x, _, _ = blk.block_apply(p["block"], x, kind=kind, cfg=cfg, aux=aux_m)
    return x


def _ce(logits, labels, mask):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _num_ce_chunks(cfg: ModelConfig, seq: int) -> int:
    """Resolved chunk count: a divisor of ``seq`` near the target."""
    want = cfg.ce_chunks
    if want == 0:  # auto: ~16M logits elements per chunk
        want = max(1, (seq * cfg.vocab_padded) // (1 << 24))
    want = min(want, seq)
    for nc in range(want, 0, -1):
        if seq % nc == 0:
            return nc
    return 1


def _ce_stream(params, h, labels, mask, cfg: ModelConfig, ctx):
    """Streaming cross-entropy over sequence chunks (§Perf, train cells).

    The head matmul + log-softmax + gather run one [B, S/nc] slab at a time
    inside a remat'd scan, so the [B, S, vocab] f32 logits never exist —
    peak loss-side activation drops by nc (32x for the 4k x 129k deepseek
    train cell).  Chunking the SEQUENCE keeps the vocab-sharded head matmul
    layout untouched (vocab chunking would slice the sharded dim).
    """
    nc = _num_ce_chunks(cfg, h.shape[1])
    if nc <= 1:
        return _ce(_logits(params, h, cfg, ctx), labels, mask)
    b, s, d = h.shape
    sc = s // nc
    hc = jnp.moveaxis(h.reshape(b, nc, sc, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, sc), 1, 0)
    mc = jnp.moveaxis(mask.reshape(b, nc, sc), 1, 0)

    def body(carry, xs):
        nll, msum = carry
        h_c, l_c, m_c = xs
        logp = jax.nn.log_softmax(_logits(params, h_c, cfg, ctx), axis=-1)
        ll = jnp.take_along_axis(logp, l_c[..., None], axis=-1)[..., 0]
        return (nll - (ll * m_c).sum(), msum + m_c.sum()), None

    (nll, msum), _ = jax.lax.scan(
        jax.checkpoint(body), (jnp.float32(0.0), jnp.float32(0.0)),
        (hc, lc, mc),
    )
    return nll / jnp.maximum(msum, 1.0)


def loss_fn(params, batch, cfg: ModelConfig, ctx=None, chunk: int = 1024):
    aux = _make_aux(batch, cfg, ctx, chunk)
    if cfg.enc_layers:
        aux["memory"] = _encode(params, batch, cfg, aux)
    if "embeds" in batch and not cfg.enc_layers:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = _embed_tokens(params, batch["tokens"], cfg)
    x = constrain(x, ctx, ("dp", None, None))
    h, aux_loss, _ = _run_groups(params["groups"], x, cfg, aux, _decoder_groups(cfg))
    labels = batch["labels"]
    mask = batch.get("loss_mask", jnp.ones(labels.shape, jnp.float32))
    ce = _ce_stream(params, h, labels, mask, cfg, ctx)
    loss = ce + aux_loss
    metrics = {"ce": ce, "aux": aux_loss, "tokens": mask.sum()}
    if cfg.mtp and "tokens" in batch:
        h_mtp = _mtp_trunk(params, h, batch, cfg, aux)
        ce_mtp = _ce_stream(
            params, h_mtp, labels[:, 1:], mask[:, 1:], cfg, ctx
        )
        loss = loss + cfg.mtp_weight * ce_mtp
        metrics["ce_mtp"] = ce_mtp
    return loss, metrics


# ------------------------------------------------------------------- serving
def init_caches(cfg: ModelConfig, bsz: int, cache_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    groups = _decoder_groups(cfg)
    caches = []
    for kind, count in groups:
        kinds = _group_kinds(kind)
        per_layer = tuple(
            blk.block_init_cache(cfg, k, bsz, cache_len, dtype)
            if k not in ("xdec",)
            else (
                blk.block_init_cache(cfg, "attn", bsz, cache_len, dtype),
                None,  # memory kv filled at prefill
            )
            for k in kinds
        )
        stacked = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (count, *a.shape)).copy()
            if a is not None
            else None,
            per_layer,
        )
        caches.append(stacked)
    return caches


def prefill(params, batch, cfg: ModelConfig, ctx=None, chunk: int = 1024):
    """Run the prompt; returns (last-position logits, caches)."""
    aux = _make_aux(batch, cfg, ctx, chunk)
    if cfg.enc_layers:
        aux["memory"] = _encode(params, batch, cfg, aux)
        x = _embed_tokens(params, batch["tokens"], cfg)
    elif "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = _embed_tokens(params, batch["tokens"], cfg)
    x = constrain(x, ctx, ("dp", None, None))
    x, _, caches = _run_groups(
        params["groups"], x, cfg, aux, _decoder_groups(cfg), want_cache=True
    )
    logits = _logits(params, x[:, -1:, :], cfg, ctx)
    return logits, caches


def pad_caches(caches, cfg: ModelConfig, cache_len: int):
    """Grow prefill caches to ``cache_len`` so decoding can continue.

    Full-attention K/V (and MLA compressed) caches are padded along the
    sequence dim; ring-buffer (local), SSM and RG-LRU states are fixed-size;
    enc-dec memory K/V is never padded (padded zero-keys would corrupt the
    cross-attention softmax).
    """
    groups = _decoder_groups(cfg)
    out = []
    for cache, (kind, _count) in zip(caches, groups):
        kinds = _group_kinds(kind)
        new = []
        for i, k in enumerate(kinds):
            c = cache[i]
            if k in ("attn", "attn_dense", "attn_moe"):
                c = tuple(_pad_seq(x, cache_len) for x in c)
            elif k == "xdec":
                sa, mkv = c
                c = (tuple(_pad_seq(x, cache_len) for x in sa), mkv)
            new.append(c)
        out.append(tuple(new))
    return out


def _pad_seq(x, cache_len: int):
    cur = x.shape[2]  # [L, B, S, ...]
    if cur >= cache_len:
        return x
    pad = [(0, 0)] * x.ndim
    pad[2] = (0, cache_len - cur)
    return jnp.pad(x, pad)


def decode_step(params, tokens, caches, pos, cfg: ModelConfig, ctx=None,
                routes: bool = False):
    """One decode step.  tokens [B, 1]; pos scalar int32.

    ``routes=True`` also returns the experts each routed layer chose, per
    scan group a tuple over its block kinds of [layers, B, 1, top_k] (None
    for a kind that routes nothing): the record a router-output option
    gives, at no change to the computation."""
    bsz = tokens.shape[0]
    if cfg.mrope:
        positions = jnp.broadcast_to(pos, (3, bsz, 1))
    else:
        positions = jnp.broadcast_to(pos, (bsz, 1))
    aux = {"positions": positions, "ctx": ctx, "chunk": 1024, "memory": None}
    x = constrain(_embed_tokens(params, tokens, cfg), ctx, ("dp", None, None))
    groups = _decoder_groups(cfg)
    new_caches, chosen = [], []
    for gp, cache, (kind, count) in zip(params["groups"], caches, groups):
        kinds = _group_kinds(kind)
        # Blocks whose decode reads only the experts chosen get the whole
        # expert stacks and the layer's index, not the scan's slice of
        # them (every held expert of the layer, copied).
        stacks = {
            f"b{i}": {w: gp[f"b{i}"]["moe"][w] for w in moe_mod.EXPERT_WEIGHTS}
            for i, k in enumerate(kinds)
            if k == "attn_moe" and moe_mod.reads_chosen(cfg, ctx, bsz)
        }
        xs = (gp, cache)
        if stacks:
            gp = {b: {**p, "moe": {w: v for w, v in p["moe"].items()
                                   if w not in stacks[b]}} if b in stacks else p
                  for b, p in gp.items()}
            xs = (gp, cache, jnp.arange(count, dtype=jnp.int32))

        def body(carry, xs):
            h = carry
            layer_p, layer_cache, *index = xs
            new_cs, top = [], []
            for i, k in enumerate(kinds):
                b = f"b{i}"
                p = layer_p[b]
                if b in stacks:
                    p = {**p, "moe": {**p["moe"], **stacks[b]}}
                h, c, top_i = blk.block_decode(
                    p, h, kind=k, cfg=cfg, aux=aux,
                    cache=layer_cache[i], pos=pos,
                    layer=index[0] if b in stacks else None,
                )
                new_cs.append(c)
                top.append(top_i)
            return h, (tuple(new_cs), tuple(top) if routes else ())

        x, (new_cache, top) = jax.lax.scan(body, x, xs)
        new_caches.append(new_cache)
        chosen.append(top)
    logits = _logits(params, x, cfg, ctx)
    if routes:
        return logits, new_caches, chosen
    return logits, new_caches


# ------------------------------------------------------------------ counting
def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact parameter count via eval_shape; ``active_only`` scales expert
    weights by top_k/num_experts (for 6*N_active*D model flops)."""
    shapes, _ = init_shapes(cfg)
    total = 0

    def visit(path, leaf):
        nonlocal total
        n = 1
        for s in leaf.shape:
            n *= s
        if active_only and cfg.moe is not None and "moe" in str(path):
            pstr = str(path)
            if any(f"'{w}'" in pstr for w in ("w1", "w2", "w3")):
                n = int(n * cfg.moe.top_k / cfg.moe.num_experts)
        total += n

    jax.tree_util.tree_map_with_path(visit, shapes)
    return int(total)
