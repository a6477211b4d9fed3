"""The benchmark's own tests, on the CPU: counters against hand-worked
numbers, traffic determinism and mix, the trace reducer on a recorded TPU
trace, the plain references against the program at tiny sizes, and whole
driver runs with the timed path broken underneath.

    PYTHONPATH=src python -m pytest tests/chipbench -q
"""

from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

from chipbench import counts, harness, traffic, trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
TINY_LM = {"hidden_size": 4, "intermediate_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "head_dim": 2, "num_hidden_layers": 1,
           "vocab_size": 10}


# ---------------------------------------------------------------- counters
def test_fd3d_counts_by_hand():
    # 16 B and 44 operations per cell per step: 10 cells, 3 steps.
    assert counts.fd3d_bytes(10, 3) == 480
    assert counts.fd3d_flops(10, 3) == 1320


def test_lm_counts_by_hand():
    # q 4x4, k and v 4x2 each, o 4x4, mlp 3 x 4x8: 144 per layer; head 40.
    assert counts.lm_flops_per_token(TINY_LM, 2) == 2 * (144 + 40) + 1 * 4 * 2 * 2 * 3
    # layers (144 weights + 2 norms of 4) + final norm 4 + head 40 + one
    # embedding row 4, at 2 B each.
    assert counts.lm_weight_bytes(TINY_LM) == 2 * (152 + 4 + 40 + 4)
    # K and V (1 head of 2) at 2 B, for 3 positions, 1 layer.
    assert counts.lm_kv_bytes(TINY_LM, 2) == 24


def test_phi4_counts_by_hand():
    cfg = json.loads((harness.HERE / "configs" / "serve-phi4-mini.json").read_text())
    mats = 32 * 100_663_296 + 3072 * 200_064
    assert counts.lm_flops_per_token(cfg, 0) == 2 * mats + 32 * 4 * 24 * 128
    # ~7.7 GB read per decode step: all but the embedding table.
    assert 7.6e9 < counts.lm_weight_bytes(cfg) < 7.8e9


# ----------------------------------------------------------------- traffic
def test_lognormal_bin_means_keep_the_published_mean():
    bins = traffic.lognormal_bin_means(161.31, 1.0, 4)
    assert sum(bins) / 4 == pytest.approx(161.31, rel=1e-9)
    assert bins == sorted(bins) and bins[-1] > 2 * 161.31  # the tail is kept
    # one bin is the distribution itself; with no spread every bin is the mean
    assert traffic.lognormal_bin_means(50.0, 1.0, 1) == pytest.approx([50.0])
    assert traffic.lognormal_bin_means(50.0, 1e-9, 3) == pytest.approx([50.0] * 3)


def test_backlog_classes_hold_the_published_means():
    mix = json.loads((harness.HERE / "traffic" / "backlog.json").read_text())
    classes = traffic.serve_classes(mix)
    assert classes == [(30, 151), (72, 848), (138, 64), (405, 289)]
    assert abs(np.mean([p for p, _ in classes]) - mix["prompt_mean"]) < 0.5
    assert abs(np.mean([o for _, o in classes]) - mix["output_mean"]) < 0.5


@pytest.mark.parametrize("seed", [2**33 + 5, 0])
def test_serve_jobs_are_seeded_whole_and_reordered(seed):
    mix = json.loads((harness.HERE / "traffic" / "backlog.json").read_text())
    classes = traffic.serve_classes(mix)
    a = traffic.serve_jobs(mix, 51, 200_064, seed=seed)
    b = traffic.serve_jobs(mix, 51, 200_064, seed=seed)
    c = traffic.serve_jobs(mix, 51, 200_064, seed=seed + 11)
    shape = lambda job: [(len(q["prompt"]), q["new_tokens"]) for q in job]  # noqa: E731
    assert len(a) == len(c) == math.ceil(mix["jobs_per_second"] * 51)
    assert [shape(j) for j in a] == [shape(j) for j in b]
    assert all((x["prompt"] == y["prompt"]).all() for ja, jb in zip(a, b)
               for x, y in zip(ja, jb))
    for job in a + c:  # every job holds each class once
        assert sorted(shape(job)) == sorted(classes)
    assert [shape(j) for j in a] != [shape(j) for j in c]  # the seed orders
    assert len({tuple(shape(j)) for j in a}) > 1  # and each job anew
    assert all(0 <= x["prompt"].min() and x["prompt"].max() < 200_064
               for job in a for x in job)


@pytest.mark.parametrize("mix_name", ["uniform-8", "bimodal-16"])
def test_survey_traffic_is_seeded_and_ordered(mix_name):
    mix = json.loads((harness.HERE / "traffic" / f"{mix_name}.json").read_text())
    a = traffic.survey_shots(mix, (801, 801), seed=2**33 + 5, survey=3)
    assert a == traffic.survey_shots(mix, (801, 801), seed=2**33 + 5, survey=3)
    assert a != traffic.survey_shots(mix, (801, 801), seed=2**33 + 5, survey=4)
    want = [tuple(c["aperture"]) for c in mix["classes"] for _ in range(c["count"])]
    assert [s["aperture"] for s in a] == want  # classes in acquisition order
    xs = [s["src_yx"][1] for s in a]
    assert xs == sorted(xs)
    big = max(max(s["aperture"]) for s in a)
    for s in a:  # every aperture fits in the model around its source
        y, x = s["src_yx"]
        assert big // 2 <= y <= 801 - big // 2 and big // 2 <= x <= 801 - big // 2


# ------------------------------------------------------------------- trace
def test_merge_is_the_union():
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_reduce_recorded_tpu_trace():
    meta = json.loads((DATA / "trace_small.json").read_text())
    s = trace_reduce.reduce_file(str(DATA / "trace_small.xplane.pb"))
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    # the 50 ms host sleep inside the window is the longest idle gap, and
    # it is put down to the span the host was in
    name, secs = s.gaps[0]
    assert name == "chipbench.idle_probe"
    assert meta["sleep_s"] <= secs < meta["sleep_s"] + 0.01
    # the device tracer came up after the first shot had run
    secs, shots = s.time_of("module", "run_shot")
    assert shots == pytest.approx(2.0) and 0 < secs < s.window_s
    secs, n = s.time_of("op", "fd3d")
    assert n == shots * meta["nt"]  # one kernel launch per time step
    assert len(s.breakdown()["device_ops"]) <= 10


def test_programs_are_found_by_their_exact_name():
    s = trace_reduce.reduce_file(str(DATA / "trace_small.xplane.pb"))
    secs, n = s.time_of_program("jit_run_shot", 1)
    assert (secs, n) == s.time_of("module", "run_shot")
    with pytest.raises(ValueError, match="0 programs"):  # a part of the name
        s.time_of_program("run_shot", 1)
    with pytest.raises(ValueError, match="0 programs"):  # a renamed program
        s.time_of_program("jit__lambda", 4)
    s.module_s["jit_run_shot(1)"], s.module_n["jit_run_shot(1)"] = 1.0, 3.0
    with pytest.raises(ValueError, match="2 programs"):  # more than there are
        s.time_of_program("jit_run_shot", 1)
    assert s.time_of_program("jit_run_shot", 2)[1] == n + 3.0


# -------------------------------------------------------------- references
def test_survey_reference_matches_program():
    import jax.numpy as jnp

    from repro.seismic.model import SeismicModel, run_shot

    ref = harness.load_module(harness.HERE / "configs" / "survey-overthrust_ref.py")
    rng = np.random.default_rng(0)
    vel = jnp.asarray(rng.uniform(2200, 6000, (16, 20, 24)).astype(np.float32))
    src = np.array([2, 9, 11], np.int32)
    rec = np.array([[2, 9, x] for x in range(4, 20, 3)], np.int32)
    kw = dict(dx=25.0, dt=0.00175, f_peak=8.0)
    want = np.asarray(ref.shot(vel, src, rec, nt=60, width=4, decay=0.03, **kw))
    m = SeismicModel(velocity=vel, sponge=4, sponge_decay=0.03, **kw)
    for backend in ("ref", "pallas_interpret"):
        got = np.asarray(run_shot(m, jnp.asarray(src), jnp.asarray(rec), nt=60,
                                  backend=backend if backend != "pallas_interpret"
                                  or vel.shape[0] % 8 == 0 else "ref"))
        assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    ctrl = np.asarray(ref.shot(vel, src, rec, nt=60, width=4, decay=0.03,
                               dtype=jnp.bfloat16, **kw))
    assert np.abs(ctrl - want).max() > 1e-3 * np.abs(want).max()


@pytest.mark.parametrize("tied", [True, False])
def test_serve_reference_matches_program_forward(tied):
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    drv = tiny.driver("serve")
    ref = harness.load_module(harness.HERE / "configs" / "serve-phi4-mini_ref.py")
    config = dict(tiny.SERVE, tie_word_embeddings=tied)
    cfg = tiny.smoke_program_config(config)
    params = drv.make_params(cfg, 5)
    assert ("head" in params) is not tied
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, 12), jnp.int32)
    want = ref.logits(params, toks, config)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        got, _ = lm.forward(f32, {"tokens": toks[None]}, cfg.with_(dtype="float32"))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-4, rtol=2e-4)


# ----------------------------------------------------------------- faults
def _compose(cell_name, out):
    import jax

    sys.modules.setdefault("chipbench_run", harness.load_module(
        harness.HERE / "run.py", "chipbench_run"))
    run = sys.modules["chipbench_run"]
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    return run.compose(bench, cell, out, jax.devices(), tiny.PEAKS["TPU v5 lite"], False)


def test_survey_run_is_correct_and_faults_are_caught(monkeypatch):
    import repro.seismic.model as model

    drv = tiny.driver("survey")
    res = _compose("survey-uniform", drv.run(
        tiny.cpu_run("survey-overthrust", tiny.SURVEY, tiny.SURVEY_MIX, seconds=0.3)))
    assert res["correct"] and res["failed"] == 0
    assert list(res["checks"]) == ["seis_rel_err"]
    real = model.run_shot

    def altered(*a, **k):  # an answer altered where it is produced
        return real(*a, **k) * 1.01

    monkeypatch.setattr(model, "run_shot", altered)
    res = _compose("survey-uniform", drv.run(
        tiny.cpu_run("survey-overthrust", tiny.SURVEY, tiny.SURVEY_MIX, seconds=0.3)))
    assert not res["correct"]
    calls = []

    def lost(*a, **k):  # one shot never answered
        calls.append(1)
        if len(calls) == 4:
            raise RuntimeError("shot lost")
        return real(*a, **k)

    monkeypatch.setattr(model, "run_shot", lost)
    res = _compose("survey-uniform", drv.run(
        tiny.cpu_run("survey-overthrust", tiny.SURVEY, tiny.SURVEY_MIX, seconds=0.3)))
    assert not res["correct"] and res["failed"] > 0


@pytest.mark.parametrize("tied", [True, False])
def test_serve_run_is_correct_and_faults_are_caught(monkeypatch, tied):
    import jax.numpy as jnp

    import repro.launch.serve as serve

    drv = tiny.driver("serve")
    monkeypatch.setattr(drv, "program_config", tiny.smoke_program_config)
    cell, mix = "serve-backlog", tiny.JOB_MIX
    config = dict(tiny.SERVE, tie_word_embeddings=tied)
    out = drv.run(tiny.cpu_run("serve-phi4-mini", config, mix))
    res = _compose(cell, out)
    assert res["correct"], res["checks"]
    # whole jobs only: every class as often as every other
    assert out.attempted % len(traffic.serve_classes(mix)) == 0
    real = serve.generate

    def altered(*a, **k):  # a token altered where it is produced
        out = real(*a, **k)
        return out.at[0, -1].set((out[0, -1] + 97) % 256).astype(jnp.int32)

    monkeypatch.setattr(serve, "generate", altered)
    res = _compose(cell, drv.run(tiny.cpu_run("serve-phi4-mini", config, mix)))
    assert not res["correct"], res["checks"]


# ---------------------------------------------------------------- controls
def test_survey_control_fails_where_the_program_passes():
    drv = tiny.driver("survey")
    run = tiny.cpu_run("survey-overthrust", tiny.SURVEY, tiny.SURVEY_MIX, seconds=0.3)
    run.control = True
    checks = {c.name: c for c in drv.run(run).checks}
    assert checks["seis_rel_err"].ok
    assert not checks["seis_rel_err.control"].ok


def test_serve_control_reads_wider_than_the_program(monkeypatch):
    drv = tiny.driver("serve")
    monkeypatch.setattr(drv, "program_config", tiny.smoke_program_config)
    # wide enough that the top logits come close, so rounding can flip them
    wide = dict(tiny.SERVE, hidden_size=128, intermediate_size=256, head_dim=32,
                num_attention_heads=4, vocab_size=4096)
    run = tiny.cpu_run("serve-phi4-mini", wide, dict(tiny.JOB_MIX, sample_tokens=60))
    run.control = True
    checks = {c.name: c for c in drv.run(run).checks}
    assert checks["logit_gap"].ok
    assert checks["logit_gap.control"].value > checks["logit_gap"].value
