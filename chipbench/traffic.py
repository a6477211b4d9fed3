"""One generator for every traffic mix.

A mix is a data file under ``chipbench/traffic/`` (JSON) of parameters:
lengths, counts, backlog.  The seed chooses the order and the content
(source positions, token ids), never the amount of work: every seed gets
the same multiset of sizes, so runs with different seeds do the same work
and differ only in its order.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

__all__ = ["rng_for", "lognormal_bin_means", "serve_classes", "survey_shots",
           "serve_jobs"]


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any non-negative seed."""
    return np.random.default_rng([int(seed), *stream.encode()])


def lognormal_bin_means(mean: float, log_sd: float, bins: int) -> list[float]:
    """The mean of each of ``bins`` equally likely bins of a log-normal
    distribution with the given mean and log-standard deviation.  Their
    average is ``mean`` exactly: one value per bin stands for the whole
    distribution, its tail included."""
    z = NormalDist()
    edges = [-math.inf] + [z.inv_cdf(i / bins) for i in range(1, bins)] + [math.inf]
    return [mean * bins * (z.cdf(hi - log_sd) - z.cdf(lo - log_sd))
            for lo, hi in zip(edges, edges[1:])]


def serve_classes(mix: dict) -> list[tuple[int, int]]:
    """The mix's request classes, ``(prompt_len, output_len)``: the bin
    means of the published prompt and output lengths, rounded, the k-th
    shortest prompt paired with the ``output_rank_of_prompt[k]``-th
    shortest output."""
    bins = len(mix["output_rank_of_prompt"])
    p = lognormal_bin_means(mix["prompt_mean"], mix["log_sd"], bins)
    o = lognormal_bin_means(mix["output_mean"], mix["log_sd"], bins)
    return [(round(p[k]), round(o[r])) for k, r in enumerate(mix["output_rank_of_prompt"])]


def survey_shots(mix: dict, grid: tuple[int, int], seed: int, survey: int):
    """Shots of one survey, in acquisition order along a surface line.

    Returns dicts ``{"index", "aperture": (ny, nx), "src_yx": (y, x)}`` in
    full-model coordinates.  Classes come in the order the mix lists them
    (e.g. the long-offset shots contiguous at the end of the line); the
    seed moves the line across the model and jitters each source.
    """
    rng = rng_for(seed, f"survey{survey}")
    ny, nx = grid
    apertures = [tuple(c["aperture"]) for c in mix["classes"]
                 for _ in range(c["count"])]
    n = len(apertures)
    big = max(max(a) for a in apertures)
    margin = big // 2 + 1
    line_y = int(rng.integers(margin, ny - margin))
    xs = np.linspace(margin, nx - margin - 1, n)
    step = (xs[1] - xs[0]) if n > 1 else 0.0
    jitter = rng.uniform(-0.25, 0.25, n) * step
    xs = np.clip(np.round(xs + jitter), margin, nx - margin - 1).astype(int)
    return [{"index": i, "aperture": apertures[i], "src_yx": (line_y, int(xs[i]))}
            for i in range(n)]


def serve_jobs(mix: dict, seconds: float, vocab: int, seed: int) -> list[list[dict]]:
    """Jobs of one run, each one request of every class in an order the
    seed draws, as ``{"prompt": int32 array, "new_tokens"}``.  Enough jobs
    for a system 4x faster than the one the mix was sized on
    (``jobs_per_second``)."""
    rng = rng_for(seed, "requests")
    classes = serve_classes(mix)
    n = max(1, math.ceil(mix["jobs_per_second"] * seconds))
    return [[{"prompt": rng.integers(0, vocab, classes[k][0], dtype=np.int32),
              "new_tokens": classes[k][1]}
             for k in rng.permutation(len(classes))]
            for _ in range(n)]
