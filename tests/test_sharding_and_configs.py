"""Sharding rules, batch/cache partition specs, config registry + shapes."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, cells, get_config, get_smoke, input_specs
from repro.configs.base import shape_applicable
from repro.parallel.sharding import (
    DEFAULT_RULES,
    ParallelContext,
    make_context,
    spec_for,
)


def _fake_ctx():
    """Mesh-free context but with rules (spec_for works without a mesh)."""
    return ParallelContext(mesh=None)


# ------------------------------------------------------------------ rules
def test_spec_for_basic_rules():
    ctx = _fake_ctx()
    assert spec_for(("embed", "ffn"), ctx) == P("data", "model")
    assert spec_for(("vocab", "embed"), ctx) == P("model", "data")
    assert spec_for(("layers", "experts", "embed", "ffn"), ctx) == P(
        None, "model", "data", None  # ffn loses: 'model' already used
    )


def test_spec_for_duplicate_axis_guard():
    ctx = _fake_ctx()
    assert spec_for(("ffn", "ffn"), ctx) == P("model", None)


def test_config_registry_complete():
    assert len(ARCH_IDS) == 10
    for a in ARCH_IDS:
        cfg = get_config(a)
        assert cfg.name == a
        smoke = get_smoke(a)
        assert smoke.family == cfg.family
        assert smoke.param_count() < 0.05e9


@pytest.mark.parametrize(
    "arch,lo,hi",
    [
        ("moonshot-v1-16b-a3b", 15.5e9, 16.5e9),  # 15.96 B as published
        ("deepseek-v3-671b", 660e9, 700e9),
        ("qwen2-vl-2b", 1.5e9, 2.2e9),
        ("mistral-nemo-12b", 11e9, 13.5e9),
        ("minitron-4b", 4e9, 6e9),
        ("qwen1.5-32b", 32e9, 37e9),
        ("phi4-mini-3.8b", 3.8e9, 5e9),
        ("recurrentgemma-2b", 2e9, 3.2e9),
        ("mamba2-2.7b", 2.4e9, 3e9),
        ("seamless-m4t-medium", 0.7e9, 1.1e9),
    ],
)
def test_param_counts_plausible(arch, lo, hi):
    n = get_config(arch).param_count()
    assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B"


def test_active_params_moe():
    cfg = get_config("deepseek-v3-671b")
    act = cfg.active_param_count()
    assert 30e9 <= act <= 45e9  # ~37B active
    dense = get_config("mistral-nemo-12b")
    assert dense.active_param_count() == dense.param_count()


def test_cells_and_applicability():
    all_cells = cells(include_skipped=True)
    assert len(all_cells) == 40
    runnable = [c for c in all_cells if c[2]]
    assert len(runnable) == 32
    skipped = [c for c in all_cells if not c[2]]
    assert all(s[1] == "long_500k" for s in skipped)
    ok, _ = shape_applicable(get_config("mamba2-2.7b"), SHAPES["long_500k"])
    assert ok
    ok, reason = shape_applicable(get_config("qwen1.5-32b"), SHAPES["long_500k"])
    assert not ok and "quadratic" in reason


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_cover_shapes(arch):
    cfg = get_config(arch)
    for sh in SHAPES.values():
        specs = input_specs(cfg, sh)
        assert specs, (arch, sh.name)
        for v in specs.values():
            assert isinstance(v, jax.ShapeDtypeStruct)
        if sh.kind == "train":
            assert "labels" in specs
        if sh.kind == "decode":
            assert specs["tokens"].shape == (sh.global_batch, 1)
        if cfg.frontend == "vision" and sh.kind != "decode":
            assert "embeds" in specs and "positions" in specs
        if cfg.enc_layers and sh.kind != "decode":
            assert "enc_embeds" in specs


def test_vocab_padding():
    seam = get_config("seamless-m4t-medium")
    assert seam.vocab_padded % 16 == 0 and seam.vocab_padded >= seam.vocab
    mamba = get_config("mamba2-2.7b")
    assert mamba.vocab_padded % 16 == 0
    nemo = get_config("mistral-nemo-12b")
    assert nemo.vocab_padded == nemo.vocab  # already divisible


def test_scan_groups_cover_all_layers():
    for a in ARCH_IDS:
        cfg = get_config(a)
        groups = cfg.scan_groups()
        total = 0
        for kind, count in groups:
            k = len(kind.split("|")) if kind.startswith("cycle:") else 1
            total += k * count
        assert total == cfg.n_layers, a
