"""Chosen-expert decode kernel vs its pure-jnp oracle, in interpret mode
(the container is CPU; TPU is the target): the compaction of the tokens'
choices to held-expert slots, and the kernel for one or two token rows
with no, some or every slot live, in float32 and bfloat16."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_decode import chosen_experts, compact, ref
from repro.kernels.moe_decode.moe_decode import moe_decode_pallas

L, E, D, F = 3, 8, 128, 256


def _stacks(dtype, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.key(seed), 3)
    w1 = jax.random.normal(k1, (L, E, D, F)) * D**-0.5
    w3 = jax.random.normal(k2, (L, E, D, F)) * D**-0.5
    w2 = jax.random.normal(k3, (L, E, F, D)) * F**-0.5
    return tuple(w.astype(dtype) for w in (w1, w3, w2))


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def test_compact_slots():
    """Held experts [4, 12) of two tokens' choices: the chosen held ones in
    increasing order, the last repeated, and each slot's weight per token;
    with none held, expert 0 in every slot and n = 0."""
    top_i = jnp.array([[9, 2, 5], [5, 13, 11]], jnp.int32)
    top_w = jnp.array([[0.5, 0.3, 0.2], [0.6, 0.1, 0.3]], jnp.float32)
    ids, n, wts = compact(top_i, top_w, 4, 8)
    assert int(n) == 3
    np.testing.assert_array_equal(np.asarray(ids), [1, 5, 7, 7, 7, 7])
    np.testing.assert_allclose(
        np.asarray(wts)[:3], [[0.2, 0.6], [0.5, 0.0], [0.0, 0.3]])
    ids, n, wts = compact(top_i, top_w, 20, 8)
    assert int(n) == 0
    np.testing.assert_array_equal(np.asarray(ids), 0)


# (token rows, their choices): no held expert, some, and every slot live.
CHOICES = {
    "none": [[9, 11]],
    "some": [[2, 11], [2, 5]],
    "all": [[0, 7], [3, 6]],
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", list(CHOICES))
def test_kernel_matches_ref(case, dtype):
    """The kernel and the oracle agree on every layer of the stacks; in
    float32 both equal each token's weighted SwiGLU sum over its chosen
    held experts, computed one expert at a time."""
    w1, w3, w2 = _stacks(dtype)
    top_i = jnp.array(CHOICES[case], jnp.int32)
    t = top_i.shape[0]
    top_w = jax.random.uniform(jax.random.key(1), top_i.shape)
    x = jax.random.normal(jax.random.key(2), (t, D)).astype(dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for layer in range(L):
        args = (jnp.int32(layer), *compact(top_i, top_w, 0, E), x, w1, w3, w2)
        got = moe_decode_pallas(*args, interpret=True)
        want = ref.moe_decode(*args)
        assert got.dtype == dtype and got.shape == (t, D)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
        if dtype == jnp.float32:
            naive = np.zeros((t, D), np.float32)
            for r in range(t):
                for e, w in zip(CHOICES[case][r], np.asarray(top_w[r])):
                    if e < E:
                        naive[r] += w * np.asarray(_swiglu(
                            x[r], w1[layer, e], w3[layer, e], w2[layer, e]))
            np.testing.assert_allclose(np.asarray(got), naive, atol=1e-5)


@pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
def test_chosen_experts_offset(backend):
    """With the held experts at an offset, the op reads expert ``e - lo``
    of the stacks for a choice ``e``."""
    w1, w3, w2 = _stacks(jnp.float32)
    top_i = jnp.array([[10, 3, 15]], jnp.int32)
    top_w = jnp.array([[0.5, 0.25, 0.25]], jnp.float32)
    x = jax.random.normal(jax.random.key(2), (1, D))
    got = chosen_experts(x, top_i, top_w, w1, w3, w2, jnp.int32(1), lo=8,
                         backend=backend)
    want = (0.5 * _swiglu(x, w1[1, 2], w3[1, 2], w2[1, 2])
            + 0.25 * _swiglu(x, w1[1, 7], w3[1, 7], w2[1, 7]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pallas_backend_needs_a_tpu():
    """Off a TPU the compiled kernel is refused, not interpreted."""
    w1, w3, w2 = _stacks(jnp.float32)
    top_i = jnp.zeros((1, 2), jnp.int32)
    x = jnp.zeros((1, D))
    with pytest.raises(RuntimeError, match="needs a TPU"):
        chosen_experts(x, top_i, top_i.astype(jnp.float32), w1, w3, w2,
                       jnp.int32(0), lo=0, backend="pallas")
