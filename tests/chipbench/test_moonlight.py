"""serve-moonlight-16b-a3b on the CPU at toy widths: the plain reference
against the program's decode through the latent cache, the reference
following the program's expert choices, the serve driver's program config
for the published file, whole driver runs with a token or a choice altered
underneath, the control, and the decode counts by hand.

    PYTHONPATH=src python -m pytest tests/chipbench/test_moonlight.py -q
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

from chipbench import counts_mla_moe, harness  # noqa: E402

CONFIG = "serve-moonlight-16b-a3b"
# Moonlight's block at toy widths, holding the second quarter of 16 experts.
MOON = {
    "driver": "serve_routed", "program_arch": "moonshot-v1-16b-a3b-ep4", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "num_hidden_layers": 3, "vocab_size": 256, "rope_theta": 50000,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False, "max_position_embeddings": 256,
    "torch_dtype": "bfloat16", "q_lora_rank": None, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32, "n_shared_experts": 2,
    "n_routed_experts": 4, "num_experts_per_tok": 4, "routed_scaling_factor": 2.446,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "n_group": 1, "topk_group": 1,
    "deployment": {"n_routed_experts_published": 16, "first_expert_held": 4},
    "replicas": 2, "slow_factor": 1.0, "limits": {"logit_gap": 0.25, "route_ties": 0.02},
}
# Counted by hand below.
TINY_MLA = {"hidden_size": 4, "num_attention_heads": 2, "qk_nope_head_dim": 2,
            "qk_rope_head_dim": 2, "v_head_dim": 2, "kv_lora_rank": 3,
            "num_hidden_layers": 2, "first_k_dense_replace": 1, "intermediate_size": 8,
            "moe_intermediate_size": 2, "n_shared_experts": 1, "n_routed_experts": 2,
            "num_experts_per_tok": 2, "vocab_size": 10,
            "deployment": {"n_routed_experts_published": 4}}


def program_config(config: dict):
    """The program's EP4 smoke config at the sizes of ``config``."""
    from repro.configs.moonshot_v1_16b_a3b_ep4 import SMOKE
    from repro.models.config import MLAConfig

    dep = config["deployment"]
    return SMOKE.with_(
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_layers=config["num_hidden_layers"],
        vocab=config["vocab_size"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=config["tie_word_embeddings"],
        max_seq=config["max_position_embeddings"],
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=config["kv_lora_rank"],
                      qk_nope_dim=config["qk_nope_head_dim"],
                      qk_rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"]),
        moe=dataclasses.replace(
            SMOKE.moe, num_experts=dep["n_routed_experts_published"],
            top_k=config["num_experts_per_tok"], d_expert=config["moe_intermediate_size"],
            num_shared=config["n_shared_experts"],
            first_k_dense=config["first_k_dense_replace"],
            routed_scale=config["routed_scaling_factor"],
            experts_held=config["n_routed_experts"],
            expert_offset=dep["first_expert_held"]))


def _reference():
    return harness.load_module(harness.HERE / "configs" / f"{CONFIG}_ref.py")


def _driver(monkeypatch):
    """The cell's driver, with the serve driver it runs taking the toy
    program config."""
    drv = tiny.driver("serve_routed")
    monkeypatch.setattr(drv.serve, "program_config", program_config)
    return drv


def _recorded_routes(cfg, params, seq):
    """[MoE layers, S, top_k]: the experts the program's decode chose at
    each position of ``seq``, fed through the cache."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    caches = lm.init_caches(cfg, 1, len(seq), dtype=jnp.dtype(cfg.dtype))
    step = jax.jit(lambda p, t, c, i: lm.decode_step(p, t, c, i, cfg, routes=True))
    chosen = []
    for i in range(len(seq)):
        _, caches, (_, (top,)) = step(params, seq[None, i:i + 1], caches, jnp.int32(i))
        chosen.append(top[:, 0, 0])
    return jnp.stack(chosen, 1)


def _compose(out):
    import jax

    run = harness.load_module(harness.HERE / "run.py", "chipbench_run_moonlight")
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == "serve-moonlight-backlog")
    return run.compose(bench, cell, out, jax.devices(), tiny.PEAKS["TPU v5 lite"], False)


# ------------------------------------------------------------- the file
def test_published_file_maps_to_the_ep4_share():
    """The serve driver's own program config for the benchmark's file: the
    registered EP4 share, every checked size as published."""
    config = json.loads((harness.HERE / "configs" / f"{CONFIG}.json").read_text())
    cfg = tiny.driver("serve").program_config(config)
    assert cfg.name == "moonshot-v1-16b-a3b-ep4"
    assert (cfg.d_ff, cfg.moe.d_expert) == (11_264, 1408)
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.expert_offset) == (
        config["deployment"]["n_routed_experts_published"], config["n_routed_experts"],
        config["deployment"]["first_expert_held"])
    assert (cfg.moe.top_k, cfg.moe.num_shared, cfg.moe.routed_scale) == (
        config["num_experts_per_tok"], config["n_shared_experts"],
        config["routed_scaling_factor"])
    m = cfg.mla
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_dim, m.qk_rope_dim, m.v_dim) == (
        config["q_lora_rank"], config["kv_lora_rank"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"])


# ------------------------------------------------------------ reference
def test_reference_matches_decode_through_the_latent_cache():
    """The program's decode step, position by position through the MLA
    cache (the absorbed form), against the reference's full forward pass
    (the expanded form), float32 at highest precision, with a correction
    bias that changes the choice.  1e-4 leaves room for float32 rounding
    over 3 layers (errors read ~4e-7) and none for a missing term."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    cfg = program_config(MOON).with_(dtype="float32")
    params = tiny.driver("serve").make_params(cfg, 5)
    bias = params["groups"][1]["b0"]["moe"]["router_bias"]
    assert float(jnp.std(bias)) > 0.1  # drawn as the matrices are: 16 ** -0.5
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, 12), jnp.int32)
    want = _reference().logits(f32, toks, MOON)
    caches = lm.init_caches(cfg, 1, len(toks), dtype=jnp.float32)
    step = jax.jit(lambda p, t, c, i: lm.decode_step(p, t, c, i, cfg))
    got = []
    with jax.default_matmul_precision("highest"):
        for i in range(len(toks)):
            logits, caches = step(f32, toks[None, i:i + 1], caches, jnp.int32(i))
            got.append(logits[0, 0, : cfg.vocab])
    np.testing.assert_allclose(np.asarray(jnp.stack(got)), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # the bias is live: without it the reference routes otherwise
    nobias = jax.tree.map(lambda a: a, f32)
    nobias["groups"][1]["b0"]["moe"]["router_bias"] = jnp.zeros_like(bias, jnp.float32)
    other = _reference().logits(nobias, toks, MOON)
    assert np.abs(np.asarray(other - want)).max() > 1e-3


def test_reference_follows_the_programs_routing():
    """A program whose biased scores read 0.002 off (a float32 program with
    a shaken correction bias, standing in for bfloat16's errors) routes some
    near ties the other way, and its greedy tokens then lie below the plain
    reference's best; the reference that follows the choices the program
    recorded reads them at rounding, and those choices lie within the
    shake of the reference's own."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import generate

    wide = dict(MOON, hidden_size=128, intermediate_size=256, head_dim=32,
                qk_nope_head_dim=32, v_head_dim=32, kv_lora_rank=64,
                moe_intermediate_size=64, vocab_size=4096)
    cfg = program_config(wide).with_(dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          tiny.driver("serve").make_params(cfg, 8))
    moe = params["groups"][1]["b0"]["moe"]
    shaken = jax.tree.map(lambda a: a, params)
    shaken["groups"][1]["b0"]["moe"]["router_bias"] = moe["router_bias"] + (
        0.002 * jax.random.normal(jax.random.key(8), moe["router_bias"].shape))
    prompt = jnp.asarray(np.random.default_rng(8).integers(0, 4096, 6), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = generate(cfg, shaken, prompt[None], 40)[0]
        seq = jnp.concatenate([prompt, got])
        routes = _recorded_routes(cfg, shaken, seq)
    ref = _reference()
    plain = ref.logits(params, seq, wide)
    assert float(jnp.max(ref.gaps(plain[5:45], got))) > 0.03
    lg, scores = ref.forward(params, seq, wide, routes=routes)
    assert float(jnp.max(ref.gaps(lg[5:45], got))) < 1e-3
    ties = ref.route_gaps(scores, routes)
    assert 0.0 < float(jnp.max(ties)) < 0.02
    # the reference's own choices read 0
    own = jax.lax.top_k(scores, 4)[1]
    assert float(jnp.max(ref.route_gaps(scores, own))) == 0.0


def test_route_gaps_by_hand():
    import jax.numpy as jnp

    ref = _reference()
    scores = jnp.asarray([[0.9, 0.5, 0.49, 0.1], [0.9, 0.5, 0.49, 0.1]])
    chosen = jnp.asarray([[0, 1], [0, 3]])
    # row 0 is the top two; in row 1 the expert at 0.5 outranks the chosen 0.1
    np.testing.assert_allclose(np.asarray(ref.route_gaps(scores, chosen)), [0.0, 0.4],
                               atol=1e-6)


# ----------------------------------------------------------- driver runs
def test_moonlight_run_is_correct_and_faults_are_caught(monkeypatch):
    import jax.numpy as jnp

    import repro.launch.serve as serve

    drv = _driver(monkeypatch)
    res = _compose(drv.run(tiny.cpu_run(CONFIG, MOON, tiny.JOB_MIX)))
    assert res["correct"], res["checks"]
    assert list(res["metrics"]) == ["tokens_per_s", "setup_s"]
    assert list(res["checks"]) == ["logit_gap", "sampled_tokens_short", "route_ties"]
    real = serve.generate

    def altered(*a, **k):  # a token altered where it is produced
        out = real(*a, **k)
        return out.at[0, -1].set((out[0, -1] + 97) % 256).astype(jnp.int32)

    monkeypatch.setattr(serve, "generate", altered)
    res = _compose(drv.run(tiny.cpu_run(CONFIG, MOON, tiny.JOB_MIX)))
    assert not res["correct"], res["checks"]


def test_moonlight_run_catches_a_choice_far_from_a_tie(monkeypatch):
    """A program that recorded, at one position of one layer, experts it
    did not choose in place of those it chose (its best one among them left
    out) fails ``route_ties``."""
    import jax
    import jax.numpy as jnp

    drv = _driver(monkeypatch)
    real = drv._routed_decode

    def far(cfg):
        step = real(cfg)

        def altered(params, tok, caches, pos):
            logits, caches, top = step(params, tok, caches, pos)
            if int(pos) == 2:
                (dense,), (moe,) = top
                row = moe[0, 0, 0]
                others = jnp.nonzero(jax.nn.one_hot(row, cfg.moe.num_experts).sum(0) == 0,
                                     size=row.shape[0])[0]
                top = [(dense,), (moe.at[0, 0, 0].set(others.astype(row.dtype)),)]
            return logits, caches, top

        return altered

    monkeypatch.setattr(drv, "_routed_decode", far)
    checks = {c.name: c for c in drv.run(tiny.cpu_run(CONFIG, MOON, tiny.JOB_MIX)).checks}
    assert not checks["route_ties"].ok, checks["route_ties"]


def test_moonlight_control_reads_wider_than_the_program(monkeypatch):
    drv = _driver(monkeypatch)
    # wide enough that the top logits come close, so rounding can flip them
    wide = dict(MOON, hidden_size=128, intermediate_size=256, head_dim=32,
                num_attention_heads=4, num_key_value_heads=4, qk_nope_head_dim=32,
                v_head_dim=32, kv_lora_rank=64, moe_intermediate_size=64,
                vocab_size=4096)
    run = tiny.cpu_run(CONFIG, wide, dict(tiny.JOB_MIX, sample_tokens=60))
    run.control = True
    checks = {c.name: c for c in drv.run(run).checks}
    assert checks["logit_gap"].ok and checks["route_ties"].ok
    assert checks["logit_gap.control"].value > checks["logit_gap"].value
    assert checks["route_ties.control"].value > checks["route_ties"].value


# --------------------------------------------------------------- counts
def test_mla_moe_counts_by_hand():
    c = TINY_MLA
    assert counts_mla_moe.routed_per_layer(c) == 1.0  # 2 of 4 chosen, 2 held
    # matrices: attention 2 x (q 4x4 + latent 4x5 + up 3x4 + 3x4 + o 4x4 = 92),
    # dense 3 x 4x8 = 96, one MoE layer of shared 3 x 4x2 and one routed
    # expert 24, head 40: 368 bf16; the router 4x4 float32 and its bias 4.
    # norms 2 x (4 + 4 + 3) + 4 and an embedding row 4; latent 3 + rope 2
    # per position and layer, pos + 1 = 4 read and one written.
    assert counts_mla_moe.mla_moe_decode_bytes(c, 3) == (
        2 * (368 + 26 + 4) + 4 * (16 + 4) + 2 * 5 * 2 * 5)
    # 2 per matrix weight (the router's too); scores 2 x 2 x (3 + 2) and the
    # latent sum 2 x 2 x 3 per position and layer, 4 positions, 2 layers.
    assert counts_mla_moe.mla_moe_flops_per_token(c, 3) == 2 * (368 + 16) + 2 * 32 * 4


def test_moonlight_counts_by_hand():
    c = json.loads((harness.HERE / "configs" / f"{CONFIG}.json").read_text())
    assert counts_mla_moe.routed_per_layer(c) == 1.5
    # non-expert weights 2.45 GB (MLA 0.74, shared 0.90, head 0.67, dense
    # 0.14), 1.5 experts of 8.65 M weights in 26 layers 0.67 GB, the router
    # 0.014 GB: ~3.14 GB a launch
    assert 3.13e9 < counts_mla_moe.mla_moe_decode_bytes(c, 0) < 3.15e9
    per_pos = 27 * 576 * 2
    assert counts_mla_moe.mla_moe_decode_bytes(c, 10) - counts_mla_moe.mla_moe_decode_bytes(
        c, 0) == pytest.approx(10 * per_pos)
