"""Logical-axis -> mesh-axis sharding rules (MaxText-style, simplified).

Weights carry *logical* axis names (see ``repro.models.layers.param``); this
module maps them onto the production mesh:

  'model' axis : tensor parallelism (attention heads, ffn, experts, vocab)
  'data'  axis : FSDP — the non-TP weight dim is sharded over 'data' so
                 per-device weight memory scales with the full pod; XLA
                 inserts the all-gather per scan step.
  'pod'   axis : pure data parallelism across pods (weights replicated,
                 gradients all-reduced) — cross-pod DCI links are slow, so
                 nothing weight-related crosses them.

Batch/activations: batch dim over ('pod', 'data').
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "ParallelContext",
    "make_context",
    "spec_for",
    "shardings_for",
]


# logical axis -> mesh axis (None = replicate)
DEFAULT_RULES: dict[str, str | None] = {
    "vocab": "model",
    "embed": "data",      # FSDP dim
    "ffn": "model",
    "heads": "model",
    "kv": "model",
    "experts": "model",
    "lora": None,
    "layers": None,
    "state": None,
    None: None,
}


@dataclass(frozen=True)
class ParallelContext:
    mesh: Mesh | None
    dp_axes: tuple[str, ...] = ("data",)  # batch axes (('pod','data') multi-pod)
    tp_axis: str = "model"
    # mesh axes the EXPERT dim is sharded over.  Training: ("model",) — EP
    # folded into TP, weights additionally FSDP'd over 'data'.  Serving
    # (serve_context): ("data", "model") — full EP across the mesh, token
    # replication + global psum instead of per-layer weight gathers.
    ep_axes: tuple[str, ...] = ("model",)
    rules: tuple[tuple[str | None, str | None], ...] = tuple(
        DEFAULT_RULES.items()
    )

    def rule(self, logical: str | None) -> str | None:
        for k, v in self.rules:
            if k == logical:
                return v
        return None

    @property
    def batch_spec(self) -> P:
        return P(self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0])


def make_context(
    mesh: Mesh | None, rules: dict[str, str | None] | None = None
) -> ParallelContext:
    if mesh is None:
        return ParallelContext(mesh=None)
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    return ParallelContext(mesh=mesh, dp_axes=dp, rules=tuple(merged.items()))


def serve_context(mesh: Mesh | None, num_experts: int = 0) -> ParallelContext:
    """Inference parameter layout (§Perf hillclimb, deepseek decode cell).

    Training FSDP shards a weight dim over 'data', which forces an
    all-gather of the FULL parameter bank per layer per DECODE step — for
    deepseek-v3 that is ~167 GB/device/token of pure collective traffic.
    Serving instead:

      * dense weights: TP over 'model', REPLICATED over 'data' (params/16
        fits HBM for every assigned arch once experts are excluded);
      * expert weights: full EP over ('data' x 'model') when the expert
        count divides the mesh (256 experts / 256 chips for deepseek-v3);
        decode-token dispatch replicates the (tiny) token batch instead of
        gathering the (huge) weights.
    """
    if mesh is None:
        return ParallelContext(mesh=None)
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    # Widest EP grid the expert count divides (experts may stay replicated
    # across 'pod' — 2 copies of the expert bank still fit).
    ep_axes = ("model",)
    for cand in ((*dp, "model"), ("data", "model")):
        size = 1
        for a in cand:
            if a not in mesh.axis_names:
                size = 0
                break
            size *= mesh.shape[a]
        if size and num_experts > 0 and num_experts % size == 0:
            ep_axes = cand
            break
    rules = dict(DEFAULT_RULES)
    rules["embed"] = None  # no FSDP dim at serving time
    if len(ep_axes) > 1:
        rules["experts"] = ep_axes
    return ParallelContext(
        mesh=mesh, dp_axes=dp, ep_axes=ep_axes, rules=tuple(rules.items())
    )


def spec_for(
    axes: tuple[str | None, ...],
    ctx: ParallelContext,
    shape: tuple[int, ...] | None = None,
) -> P:
    """PartitionSpec for one param from its logical axes.

    Guards against (a) using the same mesh axis twice (e.g. a [ffn, ffn]
    square weight — the second occurrence is replicated) and (b) dims not
    divisible by the mesh-axis size when ``shape`` is given (replicated
    instead of relying on GSPMD padding).
    """
    used: set[str] = set()
    out = []
    for i, a in enumerate(axes):
        m = ctx.rule(a)
        parts = (m,) if isinstance(m, str) else tuple(m or ())
        if parts and shape is not None and ctx.mesh is not None:
            size = 1
            for ax in parts:
                size *= ctx.mesh.shape[ax]
            if shape[i] % size != 0:
                parts = ()
        if not parts or any(ax in used for ax in parts):
            out.append(None)
        else:
            out.append(parts if len(parts) > 1 else parts[0])
            used.update(parts)
    return P(*out)


def shardings_for(spec_tree, ctx: ParallelContext, shapes=None):
    """Tree of logical-axes tuples -> tree of NamedSharding (or None mesh).

    ``shapes``: optional matching tree with ``.shape``-carrying leaves
    (arrays or ShapeDtypeStruct) enabling the divisibility guard.
    """
    if ctx.mesh is None:
        return jax.tree.map(
            lambda axes: None, spec_tree, is_leaf=_is_axes
        )
    if shapes is None:
        return jax.tree.map(
            lambda axes: NamedSharding(ctx.mesh, spec_for(axes, ctx)),
            spec_tree,
            is_leaf=_is_axes,
        )
    flat_a, treedef = jax.tree_util.tree_flatten(spec_tree, is_leaf=_is_axes)
    flat_s = treedef.flatten_up_to(shapes)
    return treedef.unflatten(
        [
            NamedSharding(ctx.mesh, spec_for(a, ctx, s.shape))
            for a, s in zip(flat_a, flat_s)
        ]
    )


def _is_axes(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x)


def constrain(x, ctx: ParallelContext | None, dims: tuple[str | None, ...]):
    """Activation sharding constraint.  ``dims``: per-dim 'dp' | 'tp' | None.

    Without explicit anchors XLA's sharding propagation can (and does) drop
    the batch sharding at the embedding/logits boundaries, materialising
    full-batch × full-vocab tensors.  This pins the canonical activation
    layout: batch over the DP axes, feature/vocab over 'model', replicated
    elsewhere.  Dims that don't divide evenly are left unconstrained.
    """
    if ctx is None or ctx.mesh is None:
        return x
    spec = []
    for i, d in enumerate(dims):
        if d == "dp":
            size = 1
            for a in ctx.dp_axes:
                size *= ctx.mesh.shape[a]
            if x.shape[i] % size == 0:
                spec.append(ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0])
            else:
                spec.append(None)
        elif d == "tp":
            tpn = ctx.mesh.shape[ctx.tp_axis]
            spec.append(ctx.tp_axis if x.shape[i] % tpn == 0 else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*spec))
    )
