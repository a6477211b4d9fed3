"""Serve cells of a model with routed experts: ``serve``'s run, with the
reference routing each sampled request as the program routed it.

The served tokens do not say which experts a position chose.  Where two
experts' biased scores lie within rounding of each other, a bfloat16
program and the float32 reference may choose differently, and the tokens
after such a choice are the model on another path: their logits part from
the reference's by far more than rounding.  So for each sampled request
the program's decode step with its routing record on
(``lm.decode_step(..., routes=True)``) runs again along the served
sequence, at the served cache length, and the reference
(``forward(..., routes=)``) follows those choices.  The check
``route_ties`` then holds the choices themselves: the widest margin, over
the sample's positions and expert layers, by which an expert the program
left out outranks one it chose in the reference's own biased scores.

With ``Run.control`` the float8 reference takes the program's place on the
same path: ``logit_gap.control`` reads its arithmetic, and
``route_ties.control`` the widest margin of its own top k in the float32
reference's scores.
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench import traffic
from chipbench.drivers import serve
from chipbench.harness import Check


def run(r):
    ref = Following(r.reference(), r)
    r.reference = lambda: ref  # serve.run takes its reference from the run
    out = serve.run(r)
    limit = r.config["limits"]["route_ties"]
    out.checks.append(Check("route_ties", ref.widest, limit))
    if r.control:
        out.checks.append(Check("route_ties.control", ref.widest_control, limit))
    r.log(f"program re-run: {ref.retraced} of {ref.tokens} sampled tokens as served")
    return out


@functools.lru_cache(maxsize=None)
def _routed_decode(cfg):
    """The served decode step (``launch.serve.make_decode``) with the
    routing record on."""
    import jax

    from repro.models import lm

    def routed_decode_step(params, tok, caches, pos):
        return lm.decode_step(params, tok, caches, pos, cfg, routes=True)

    return jax.jit(routed_decode_step, donate_argnums=(2,))


class Following:
    """The cell's reference as ``serve.run`` calls it (``logits``, ``gaps``),
    following the program's expert choices for the request whose sequence
    it is given: the jobs of the run, drawn again from its seed, say which
    request that is."""

    def __init__(self, plain, r):
        self.plain, self.gaps = plain, plain.gaps
        jobs = traffic.serve_jobs(r.mix, r.seconds, r.config["vocab_size"], r.seed)
        self.lengths = {q["prompt"].tobytes(): (len(q["prompt"]), q["new_tokens"])
                        for job in jobs for q in job}
        self.prompt_lens = sorted({s for s, _ in self.lengths.values()})
        self.widest = self.widest_control = 0.0
        self.retraced = self.tokens = 0
        self._seen = None  # (sequence, routes, positions run, float32 scores)

    def _request(self, seq: np.ndarray) -> tuple[int, int]:
        for n in self.prompt_lens:
            if seq[:n].tobytes() in self.lengths:
                return self.lengths[seq[:n].tobytes()]
        raise KeyError("a sequence of no request this run sent")

    def _program_routes(self, params, seq: np.ndarray, config: dict):
        """[MoE layers, len(seq), top_k] experts the program chose at each
        position it ran for this request (-1 beyond), and that count."""
        import jax.numpy as jnp

        from repro.models import lm

        s, n = self._request(seq)
        cfg = serve.program_config(config)
        step = _routed_decode(cfg)
        caches = lm.init_caches(cfg, 1, s + n)
        toks = jnp.asarray(seq[: s + n], jnp.int32)[None, :, None]
        chosen, nxt = [], []
        for i in range(s + n - 1):
            logits, caches, top = step(params, toks[:, i], caches, jnp.int32(i))
            chosen.append(jnp.concatenate([t.reshape(t.shape[0], -1)
                                           for group in top for t in group if t is not None]))
            nxt.append(jnp.argmax(logits[0, -1]))
        # the program's own greedy tokens again, beside the served ones
        again = np.asarray(jnp.stack(nxt[s - 1:]))
        self.retraced += int((again == seq[s:s + n]).sum())
        self.tokens += n
        routes = np.full((chosen[0].shape[0], len(seq), chosen[0].shape[1]), -1, np.int32)
        routes[:, : s + n - 1] = np.asarray(jnp.stack(chosen, 1))
        return jnp.asarray(routes), s + n - 1

    def logits(self, params, seq, config: dict, fp8: bool = False):
        import jax
        import jax.numpy as jnp

        seq = np.asarray(seq, np.int32)
        if self._seen is None or not np.array_equal(self._seen[0], seq):
            routes, ran = self._program_routes(params, seq, config)
            lg, scores = self.plain.forward(params, jnp.asarray(seq), config, routes=routes)
            self._seen = (seq, routes, ran, scores)
            self.widest = max(self.widest, float(jnp.max(
                self.plain.route_gaps(scores[:, :ran], routes[:, :ran]))))
            if not fp8:
                return lg
        _, routes, ran, ref_scores = self._seen
        lg, scores = self.plain.forward(params, jnp.asarray(seq), config, fp8=fp8,
                                        routes=routes)
        if fp8:
            own = jax.lax.top_k(scores[:, :ran], routes.shape[-1])[1]
            self.widest_control = max(self.widest_control, float(jnp.max(
                self.plain.route_gaps(ref_scores[:, :ran], own))))
        return lg
