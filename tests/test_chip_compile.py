"""Compile-only checks for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode and the CPU backend accept: a
kernel that asks for more VMEM than the chip has, a program that does not
fit HBM, a mesh whose sharding cannot be partitioned.  These cases compile
the main path's programs at real widths for the chip.  The topology is
described inside a fixture only (never at import), so every test worker
collects the same tests and only the one running this file loads libtpu.
"""

import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs.base import get_config
from repro.kernels.fd3d.fd3d import fd3d_pallas
from repro.kernels.moe_decode import compact, moe_decode_pallas
from repro.launch.serve import make_decode
from repro.models import lm
from repro.models import moe as moe_mod
from repro.parallel.sharding import make_context, serve_context, shardings_for
from repro.seismic import model as seismic
from repro.serve.engine import abstract_caches, cache_shardings, jit_decode_step

HBM_BYTES = 16e9  # one v5e chip
CACHE_LEN = 24


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def phi4():
    return get_config("phi4-mini-3.8b")


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


@pytest.mark.parametrize("n", [128, 256])
def test_fd3d_kernel_compiles(one_chip, n):
    """The compiled kernel (not the interpreter) at survey grid sizes with
    the default block size."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = s((n, n, n))
    step = jax.jit(lambda *a: fd3d_pallas(*a, dx=10.0, interpret=False))
    compiled = step.lower(x, x, x, s((n,)), s((n, n)), s((3,), jnp.int32),
                          s(())).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_shot(one_chip, monkeypatch, nz, ny, nx, nt):
    """``run_shot`` with the compiled kernel, at survey-overthrust's
    settings.  Its default backend asks ``jax.default_backend()``, which is
    the CPU here, so the test hands it the kernel itself."""
    monkeypatch.setattr(
        seismic, "fd3d_step",
        lambda *a, dx, backend=None: fd3d_pallas(*a, dx=dx, interpret=False))
    vel = jax.ShapeDtypeStruct((nz, ny, nx), jnp.float32, sharding=one_chip)
    model = seismic.SeismicModel(velocity=vel, dx=25.0, dt=0.00175,
                                 f_peak=8.0, sponge=16, sponge_decay=0.008)
    src = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=one_chip)
    rec = jax.ShapeDtypeStruct((64, 3), jnp.int32, sharding=one_chip)
    return seismic.run_shot.lower(model, src, rec, nt=nt).compile().as_text()


def _field_ops(body: str, cells: int) -> Counter:
    """Opcodes of the instructions in an HLO computation that produce an
    array of at least ``cells`` elements (views and tuples aside)."""
    ops = Counter()
    for line in body.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([a-z][a-z0-9-]*)\(", line)
        if not m or m.group(2) in ("parameter", "get-tuple-element", "tuple",
                                   "bitcast"):
            continue
        dims = re.findall(r"[a-z]\d+\[([\d,]+)\]", m.group(1))
        if any(np.prod([int(d) for d in ds.split(",")]) >= cells
               for ds in dims if ds):
            ops[m.group(2)] += 1
    return ops


def test_shot_loop_is_one_kernel_pass_per_step(one_chip, monkeypatch):
    """At survey-uniform's shape the loop body runs two steps, and each is
    the kernel and the pad of its input: no taper pass, no select of the
    initial zeros and no copy of a field between steps."""
    shape = (192, 256, 256)
    hlo = _compile_shot(one_chip, monkeypatch, *shape, nt=2000)
    bodies = re.findall(r" while\(.*?body=%([\w.-]+)", hlo)
    assert len(bodies) == 1
    start = hlo.index(f"\n%{bodies[0]} ")
    body = hlo[start:hlo.index("\n}", start)]
    assert _field_ops(body, int(np.prod(shape))) == Counter(
        {"custom-call": 2, "pad": 2})
    assert body.count('custom_call_target="tpu_custom_call"') == 2
    assert "copy-start" not in body


def test_shot_compiles_at_512_aperture(one_chip, monkeypatch):
    """The bimodal mix's 512 x 512 aperture over the full depth fits the
    kernel's VMEM limit with the taper plane."""
    hlo = _compile_shot(one_chip, monkeypatch, 192, 512, 512, nt=2000)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2


def _compile_decode(cfg, one_chip):
    """The served decode step at full width and depth, one token, for one
    described chip."""
    shapes, _ = lm.init_shapes(cfg)
    params = _placed(shapes, one_chip)
    caches = _placed(abstract_caches(cfg, 1, CACHE_LEN), one_chip)
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return make_decode(cfg).lower(params, tok, caches, pos).compile()


def _used(mem) -> int:
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _opcodes(hlo: str) -> Counter:
    """Opcodes of every instruction of every computation."""
    return Counter(re.findall(r"(?m)^\s*(?:ROOT )?%\S+ = .+? ([a-z][a-z0-9-]*)\(",
                              hlo))


# The compiled phi4-mini decode step before the MoE decode entry existed
# (commit 52d5efa), whose program the change leaves as it was.
PHI4_DECODE_OPS = Counter({
    "add": 31, "bitcast": 27, "broadcast": 25, "compare": 4, "constant": 47,
    "convert": 64, "convolution": 2, "copy": 11, "copy-done": 3,
    "copy-start": 3, "cosine": 1, "custom-call": 2, "divide": 2,
    "dynamic-slice": 12, "dynamic-update-slice": 4, "exponential": 3,
    "fusion": 34, "get-tuple-element": 39, "iota": 1, "maximum": 3,
    "multiply": 32, "negate": 1, "pad": 4, "parameter": 133, "reduce": 16,
    "reshape": 8, "rsqrt": 3, "select": 3, "sine": 1, "slice": 4,
    "subtract": 4, "tuple": 9, "while": 1,
})


def test_phi4_decode_compiles_one_chip(one_chip, phi4):
    """Full-width, full-depth phi4-mini decode step fits one chip's HBM, and
    is the program it was before the MoE decode entry (no expert group, so
    its layer scan is untouched): the same instructions, op for op."""
    compiled = _compile_decode(phi4, one_chip)
    mem = compiled.memory_analysis()
    assert 8e9 < mem.argument_size_in_bytes and _used(mem) < HBM_BYTES
    assert _opcodes(compiled.as_text()) == PHI4_DECODE_OPS
    assert mem.temp_size_in_bytes == 3_565_568


@pytest.fixture(scope="module")
def moonlight_decode(one_chip):
    """Moonlight-16B-A3B's EP4 share decode, compiled with the chosen-expert
    kernel in place (the op's default backend asks
    ``jax.default_backend()``, which is the CPU here)."""
    def kernel(x, top_i, top_w, w1, w3, w2, layer, *, lo):
        slots = compact(top_i, top_w, lo, w1.shape[1])
        return moe_decode_pallas(layer, *slots, x, w1, w3, w2, interpret=False)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe_mod, "chosen_experts", kernel)
        return _compile_decode(get_config("moonshot-v1-16b-a3b-ep4"), one_chip)


def test_moonlight_ep4_decode_compiles_one_chip(moonlight_decode):
    """Moonlight-16B-A3B's EP4 share (16 of 64 experts, full depth and
    vocabulary, published widths): the served decode step fits one chip,
    with its 10.3 GB of weights."""
    mem = moonlight_decode.memory_analysis()
    assert 10e9 < mem.argument_size_in_bytes and _used(mem) < HBM_BYTES
    assert "/moe/" in moonlight_decode.as_text() and "/mla/" in moonlight_decode.as_text()


def test_moonlight_ep4_decode_reads_only_chosen_experts(moonlight_decode):
    """The routed experts of every MoE layer are the kernel, reading from
    the whole [26, 16, 2048, 1408] stacks: no instruction makes a layer's
    slice of the held experts (16 of them) or computes over all of them, no
    stack is copied, and the temporaries are no larger than the 1,129,472
    bytes of the select-compute-scatter program before it (commit 52d5efa,
    the same compile)."""
    hlo = moonlight_decode.as_text()
    assert not re.search(r"\[(1,)?16,(2048,1408|1408,2048)\]", hlo)
    assert not re.search(r"= bf16\[26,16,\d+,\d+\]\S* (copy|copy-start|fusion)\(", hlo)
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', hlo)
    assert kernels and all("/moe/experts/" in k for k in kernels)
    assert moonlight_decode.memory_analysis().temp_size_in_bytes <= 1_129_472


def test_phi4_sharded_decode_compiles_1x4(topo, phi4):
    """Serving layout on a (data=1, model=4) mesh of the described chips:
    each device holds about a quarter of the weights."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    ctx = serve_context(mesh)
    shapes, specs = lm.init_shapes(phi4)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, shardings_for(specs, ctx, shapes),
    )
    caches = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract_caches(phi4, 1, CACHE_LEN),
        cache_shardings(phi4, ctx, 1, CACHE_LEN),
    )
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32,
                               sharding=NamedSharding(mesh, P(None, None)))
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
    step = jit_decode_step(phi4, make_context(mesh), 1, CACHE_LEN)
    compiled = step.lower(params, tok, caches, pos).compile()
    total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert 0.2 * total < per_device < 0.3 * total
