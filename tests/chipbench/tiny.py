"""Tiny configurations and a CPU ``Run`` for the benchmark's own tests.

The command refuses a CPU; these helpers drive a cell's driver directly,
past the device check, at sizes a CPU test run can hold.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402
from chipbench.peaks import PEAKS  # noqa: E402

SURVEY = {
    "driver": "survey", "nz": 16, "ny": 56, "nx": 56, "spacing_m": 25.0,
    "vmin_m_per_s": 2200.0, "vmax_m_per_s": 6000.0, "layers": 4,
    "lateral": 0.05, "dip": 0.15, "dt_s": 0.00175, "f_peak_hz": 8.0,
    "sponge": 4, "sponge_decay": 0.03, "src_depth": 2, "rec_depth": 2,
    "receivers": 8, "limits": {"seis_rel_err": 1e-4},
}
SURVEY_MIX = {"kind": "survey", "nt": 40, "sample": 2,
              "classes": [{"aperture": [24, 24], "count": 3},
                          {"aperture": [32, 32], "count": 1}]}

SERVE = {
    "driver": "serve", "program_arch": "phi4-mini-3.8b", "hidden_size": 48,
    "intermediate_size": 96, "num_attention_heads": 6, "num_key_value_heads": 2,
    "head_dim": 8, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "max_position_embeddings": 256, "torch_dtype": "bfloat16", "replicas": 2,
    "slow_factor": 1.0, "limits": {"logit_gap": 0.25},
}
# Classes (prompt, output): (3, 4), (4, 8), (8, 3).
JOB_MIX = {"kind": "serve", "prompt_mean": 5.0, "output_mean": 5.0,
           "log_sd": 0.5, "output_rank_of_prompt": [1, 2, 0], "backlog": 4,
           "jobs_per_second": 40.0, "sample_tokens": 12,
           "trace_after": 0.2, "trace_seconds": 0.3}


def cpu_run(config_name: str, config: dict, mix: dict, seed: int = 7,
            seconds: float = 1.0, chips: int = 1, tmp: Path | None = None):
    import jax

    cell = {"name": "tiny", "config": config_name, "traffic": "tiny", "chips": chips}
    return harness.Run(cell=cell, config=config, mix=mix, seed=seed,
                       seconds=seconds, trace=False, devices=jax.devices(),
                       peaks=PEAKS["TPU v5 lite"], t_start=time.perf_counter(),
                       work_dir=tmp or ROOT / ".chipbench_run")


def driver(name: str):
    return harness.load_module(harness.HERE / "drivers" / f"{name}.py",
                               f"chipbench_driver_{name}")


def smoke_program_config(config: dict):
    """The program's phi4-mini smoke config at the sizes of ``config``."""
    from repro.configs.phi4_mini_3_8b import SMOKE

    return SMOKE.with_(
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_layers=config["num_hidden_layers"],
        vocab=config["vocab_size"], norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        max_seq=config["max_position_embeddings"])
