"""Mixture-of-Experts layer with expert parallelism over the 'model' axis.

Design (baseline, recorded as such in EXPERIMENTS.md §Perf):

* Routing (softmax top-k, or DeepSeek-V3's sigmoid scores with a correction
  bias that only chooses; the chosen weights normalised and scaled by
  ``routed_scale``) happens in the auto-sharded (pjit) world — logits are
  tiny.
* Expert compute runs inside ``shard_map``: activations are **replicated
  across the TP/EP ('model') axis** (exactly what Megatron-style TP leaves
  between blocks), so each EP rank simply *selects* the tokens routed to its
  local experts into a fixed-capacity buffer ``[E_loc, C, d]``, runs the gated
  MLP as one batched einsum, scatter-adds weighted outputs into a local
  [tokens, d] partial, and a single ``psum`` over 'model' combines partials —
  the same collective volume as one TP all-reduce.  (The all-to-all dispatch
  variant is the §Perf hillclimb.)
* Under a mesh, tokens beyond an expert's capacity ``C =
  ceil(tokens*top_k/E * cf)`` are dropped (standard GShard semantics).  The
  mesh-free path drops nothing: its capacity is the token count.
* Decode has an entry of its own, ``moe_decode``.  Mesh-free, with fewer
  expert slots (``tokens * top_k``) than held experts, it reads only the
  held experts the tokens chose, from the whole ``[L, E, ...]`` stacks by
  layer and expert index (``kernels/moe_decode``): the served path, where
  a one-token launch needs 1.5 of 16 held experts on average.  Otherwise
  it is ``moe_apply``.  Prefill and training use ``moe_apply``.
* A layer holds the experts ``[expert_offset, expert_offset + held)`` of the
  ``num_experts`` the router scores: one device set's share of an
  expert-parallel deployment computes that share's part of the result.
* The shared expert (DeepSeek) is a TP-sharded dense MLP folded into the SAME
  psum, costing no extra collective.

``moe_dense_ref`` is the held-experts-dense oracle used by unit tests.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.spans import COUNTERS
from repro.kernels.moe_decode import chosen_experts

from .config import ModelConfig, MoEConfig
from .layers import ksplit, param

__all__ = [
    "moe_params",
    "route",
    "moe_apply",
    "moe_decode",
    "reads_chosen",
    "moe_dense_ref",
    "aux_load_balance_loss",
]


def moe_params(key, cfg: ModelConfig) -> dict:
    m: MoEConfig = cfg.moe
    d = cfg.d_model
    f, e = m.d_expert, m.held
    ks = ksplit(key, 6)
    p = {
        "router": param(ks[0], (d, m.num_experts), ("embed", None), dtype=jnp.float32),
        "w1": param(ks[1], (e, d, f), ("experts", "embed", "ffn")),
        "w3": param(ks[2], (e, d, f), ("experts", "embed", "ffn")),
        "w2": param(ks[3], (e, f, d), ("experts", "ffn", "embed")),
    }
    if m.scoring == "sigmoid":
        # One per routed expert, held as an [E, 1] column (published as
        # [E]; ROADMAP lists the departure).
        p["router_bias"] = param(key, (m.num_experts, 1), (None, None),
                                 dtype=jnp.float32, init="zeros")
    if m.num_shared:
        fs = (m.d_shared or f) * m.num_shared
        p["ws1"] = param(ks[4], (d, fs), ("embed", "ffn"))
        p["ws3"] = param(ks[5], (d, fs), ("embed", "ffn"))
        p["ws2"] = param(ks[4], (fs, d), ("ffn", "embed"))
    return p


def route(router_w: jax.Array, x: jax.Array, m: MoEConfig, bias=None):
    """Top-k routing over all ``num_experts``, in float32.  ``bias`` [E, 1]
    is added to the scores to choose the experts only.  Returns (top_idx
    [B,S,k], top_w [B,S,k], scores [B,S,E])."""
    with jax.named_scope("route"):
        logits = jnp.einsum(
            "bsd,de->bse", x.astype(jnp.float32), router_w.astype(jnp.float32)
        )
        if m.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
        pick = scores if bias is None else scores + bias.reshape(-1)
        _, top_i = lax.top_k(pick, m.top_k)
        top_w = jnp.take_along_axis(scores, top_i, axis=-1)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        top_w = top_w * m.routed_scale
        return top_i, top_w.astype(x.dtype), scores


def aux_load_balance_loss(probs: jax.Array, top_i: jax.Array, m: MoEConfig):
    """Switch-style load-balance auxiliary loss."""
    e = m.num_experts
    counts = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_probs = probs.mean(axis=(0, 1))
    return e * jnp.sum(frac_tokens * frac_probs) * m.aux_loss_coef


@jax.named_scope("experts")
def _expert_compute(xbuf, w1, w3, w2, act):
    h = jnp.einsum("ecd,edf->ecf", xbuf, w1)
    u = jnp.einsum("ecd,edf->ecf", xbuf, w3)
    h = act(h) * u
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _dispatch_local(
    x2d: jax.Array,  # [T, d] local tokens (flattened b*s)
    top_i: jax.Array,  # [T, k]
    top_w: jax.Array,  # [T, k]
    w1, w3, w2,  # [E_loc, ...] local expert weights
    *,
    m: MoEConfig,
    lo: jax.Array,
    cap: int | None,
    act,
) -> jax.Array:
    """Select->compute->scatter-add for the experts [lo, lo + E_loc).
    ``cap`` slots per expert, None for the token count (nothing dropped).
    Returns the [T, d] partial."""
    t, d_model = x2d.shape
    e_loc = w1.shape[0]
    if cap is None:
        cap = t

    eid = top_i.reshape(-1)  # [T*k]
    wgt = top_w.reshape(-1)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), m.top_k)
    local_e = eid - lo
    mine = (local_e >= 0) & (local_e < e_loc)
    sort_key = jnp.where(mine, local_e, e_loc)  # strangers sort last
    order = jnp.argsort(sort_key, stable=True)
    key_sorted = sort_key[order]
    starts = jnp.searchsorted(key_sorted, jnp.arange(e_loc + 1))
    slot_sorted = jnp.arange(key_sorted.shape[0], dtype=jnp.int32) - starts[
        jnp.clip(key_sorted, 0, e_loc)
    ].astype(jnp.int32)
    ok = (key_sorted < e_loc) & (slot_sorted < cap)
    le_s = jnp.clip(key_sorted, 0, e_loc - 1)
    tok_s = tok[order]
    wgt_s = wgt[order]
    # gather tokens into the capacity buffer
    buf = jnp.zeros((e_loc, cap, d_model), x2d.dtype)
    buf = buf.at[
        jnp.where(ok, le_s, e_loc - 1),
        jnp.where(ok, slot_sorted, cap),  # cap -> dropped
    ].set(x2d[tok_s], mode="drop")
    ybuf = _expert_compute(buf, w1, w3, w2, act)
    # scatter-add weighted outputs back to token order
    out = jnp.zeros((t, d_model), x2d.dtype)
    vals = ybuf[le_s, jnp.clip(slot_sorted, 0, cap - 1)] * wgt_s[:, None]
    out = out.at[jnp.where(ok, tok_s, t)].add(vals, mode="drop")
    return out


def moe_apply(
    params: dict,
    x: jax.Array,  # [B, S, d]
    top_i: jax.Array,
    top_w: jax.Array,
    cfg: ModelConfig,
    ctx=None,  # ParallelContext | None
    act=jax.nn.silu,
) -> jax.Array:
    """Expert-parallel MoE forward (+ shared expert).

    Two device layouts, selected by ``ctx.ep_axes``:

    * ``("model",)`` (training): experts sharded over TP, activations
      replicated across 'model'; each rank selects its experts' tokens,
      computes, and one psum over 'model' combines — collective volume of a
      single TP all-reduce.  FSDP over 'data' happens OUTSIDE (weight specs).
    * full mesh (serving, ``serve_context``): every device owns E/P whole
      experts.  Decode batches are tiny, so the TOKENS are gathered across
      'data' (MBs) instead of gathering the WEIGHTS (GBs/layer, what the
      training layout would do at decode), and one global psum combines.
    """
    m = cfg.moe
    b, s, d = x.shape
    ep_axes = getattr(ctx, "ep_axes", ("model",)) if ctx is not None else ("model",)
    dp = ctx.dp_axes if ctx is not None else ("data",)
    tp = ctx.tp_axis if ctx is not None else None
    full_ep = ctx is not None and len(ep_axes) > 1
    mesh_free = ctx is None or ctx.mesh is None

    def body(x_loc, ti_loc, tw_loc, w1, w3, w2, *shared):
        x2d = x_loc.reshape(-1, d)
        ti2 = ti_loc.reshape(-1, m.top_k)
        tw2 = tw_loc.reshape(-1, m.top_k)
        if mesh_free:
            rank = jnp.int32(0)
        elif full_ep:
            rank = jnp.int32(0)
            for ax in ep_axes:
                rank = rank * ctx.mesh.shape[ax] + lax.axis_index(ax)
        else:
            rank = lax.axis_index(tp)
        if full_ep:
            t_loc = x2d.shape[0]
            x2d = lax.all_gather(x2d, dp, axis=0, tiled=True)
            ti2 = lax.all_gather(ti2, dp, axis=0, tiled=True)
            tw2 = lax.all_gather(tw2, dp, axis=0, tiled=True)
        cap = None if mesh_free else int(math.ceil(
            x2d.shape[0] * m.top_k / m.num_experts * m.capacity_factor))
        out = _dispatch_local(
            x2d, ti2, tw2, w1, w3, w2, m=m, lo=m.expert_offset + rank * w1.shape[0],
            cap=cap, act=act,
        )
        if shared:
            sh = _shared(x2d, *shared, act)
            if full_ep:
                # shared weights are sharded over 'model' only, so every
                # 'data' rank computes the same partial: pre-scale so the
                # global psum does not multiply it by |data|.
                dp_n = 1
                for ax in dp:
                    dp_n *= ctx.mesh.shape[ax]
                sh = sh / dp_n
            out = out + sh
        if not mesh_free:
            out = lax.psum(out, ep_axes if full_ep else tp)
            if full_ep:
                start = (lax.axis_index(dp[-1]) if len(dp) == 1 else (
                    lax.axis_index(dp[0]) * ctx.mesh.shape[dp[1]]
                    + lax.axis_index(dp[1])
                )) * t_loc
                out = lax.dynamic_slice_in_dim(out, start, t_loc, 0)
        return out.reshape(x_loc.shape)

    args = [x, top_i, top_w, params["w1"], params["w3"], params["w2"]]
    if m.num_shared:
        args += [params["ws1"], params["ws3"], params["ws2"]]

    if mesh_free:
        return body(*args)

    ep_spec = tuple(ep_axes) if full_ep else tp
    in_specs = [
        P(dp, None, None),  # x: replicated over model
        P(dp, None, None),  # top_i
        P(dp, None, None),  # top_w
        P(ep_spec, None, None),  # w1
        P(ep_spec, None, None),  # w3
        P(ep_spec, None, None),  # w2
    ]
    if m.num_shared:
        in_specs += [P(None, tp), P(None, tp), P(tp, None)]  # shared: TP
    return jax.shard_map(
        body,
        mesh=ctx.mesh,
        in_specs=tuple(in_specs),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(*args)


def _shared(x2d, ws1, ws3, ws2, act):
    with jax.named_scope("shared"):
        return (act(x2d @ ws1) * (x2d @ ws3)) @ ws2


#: The routed experts' stacked weights.
EXPERT_WEIGHTS = ("w1", "w3", "w2")


def reads_chosen(cfg: ModelConfig, ctx, tokens: int) -> bool:
    """Whether ``moe_decode`` of ``tokens`` reads only the chosen experts:
    mesh-free, with fewer expert slots than held experts."""
    mesh_free = ctx is None or ctx.mesh is None
    return mesh_free and tokens * cfg.moe.top_k < cfg.moe.held


def moe_decode(
    params: dict,
    x: jax.Array,  # [B, S, d]
    top_i: jax.Array,
    top_w: jax.Array,
    cfg: ModelConfig,
    ctx=None,  # ParallelContext | None
    layer=None,
) -> jax.Array:
    """The MoE layer of a decode step (+ shared experts), with SwiGLU
    experts.  ``layer`` None: ``params``' expert weights are the layer's
    ``[E, ...]``; else they are the ``[L, E, ...]`` stacks of every layer
    and ``layer`` indexes them.  Where ``reads_chosen``, streams only the
    held experts the tokens chose and counts the layers traced so under
    ``moe.decode_chosen_layers``; else it is ``moe_apply`` (counted under
    ``moe.decode_all_held_layers``)."""
    m = cfg.moe
    b, s, d = x.shape
    ws = [params[w] for w in EXPERT_WEIGHTS]
    layers = 1 if layer is None else ws[0].shape[0]
    if not reads_chosen(cfg, ctx, b * s):
        COUNTERS.add("moe.decode_all_held_layers", layers)
        if layer is not None:
            params = dict(params, **{w: v[layer] for w, v in zip(EXPERT_WEIGHTS, ws)})
        return moe_apply(params, x, top_i, top_w, cfg, ctx)
    COUNTERS.add("moe.decode_chosen_layers", layers)
    if layer is None:
        ws, layer = [v[None] for v in ws], jnp.int32(0)
    x2d = x.reshape(-1, d)
    with jax.named_scope("experts"):
        out = chosen_experts(
            x2d, top_i.reshape(-1, m.top_k), top_w.reshape(-1, m.top_k), *ws,
            layer, lo=m.expert_offset,
        )
    if m.num_shared:
        out = out + _shared(x2d, params["ws1"], params["ws3"], params["ws2"],
                            jax.nn.silu)
    return out.reshape(x.shape)


def moe_dense_ref(params, x, cfg: ModelConfig, act=jax.nn.silu):
    """Oracle: every held expert computes every token; combine with the
    top-k weights of the held experts."""
    m = cfg.moe
    top_i, top_w, _ = route(params["router"], x, m, params.get("router_bias"))
    h = jnp.einsum("bsd,edf->bsef", x, params["w1"])
    u = jnp.einsum("bsd,edf->bsef", x, params["w3"])
    y_all = jnp.einsum("bsef,efd->bsed", act(h) * u, params["w2"])
    mask = jax.nn.one_hot(top_i - m.expert_offset, m.held, dtype=x.dtype)  # [B,S,k,E]
    w_held = (mask * top_w[..., None]).sum(-2)  # [B,S,E_held]
    out = jnp.einsum("bsed,bse->bsd", y_all, w_held)
    if m.num_shared:
        h = act(x @ params["ws1"]) * (x @ params["ws3"])
        out = out + h @ params["ws2"]
    return out
