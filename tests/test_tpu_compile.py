"""Compile rehearsals for a described (not attached) TPU v5e.

The TPU compiler ships with libtpu, so the programs the chip runs can be
compiled here without a chip: every kept Pallas kernel at real sizes (the
32k-token attention and the int8 cases included), qwen3-4b prefill and
decode at published widths, decode steps whose K/V cache stays in place
(or is sliced where it would not), and a stablelm-1.6b train step sharded
over a 2x2 mesh.  Nothing executes; these catch what interpret mode cannot —
Mosaic lowering refusals, VMEM and HBM overruns, unpartitionable programs.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and every test worker imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.hw.tpu_spec import TPU_V5E
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.launch.specs import cache_shapes, opt_shapes, params_shapes
from repro.launch.steps import build_prefill_step, build_serve_step
from repro.launch.train import jit_train_step, on_mesh
from repro.models import layers as L


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu, or it is held by another process
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, sharding), tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


I32, I8, BF16 = jnp.int32, jnp.int8, jnp.bfloat16
KERNELS = {
    "mws": (lambda s: ops.mws_bitwise(s, "and"), [((8, 512, 4096), I32)]),
    "bitserial_add_int32": (ops.bitserial_add, [((512, 4096), I32)] * 2),
    "bitserial_add_int8": (ops.bitserial_add, [((512, 4096), I8)] * 2),
    "bitserial_mul_int32": (ops.bitserial_mul, [((512, 4096), I32)] * 2),
    "bitserial_mul_int8": (ops.bitserial_mul, [((512, 4096), I8)] * 2),
    "shift_add": (ops.shift_add_mul, [((512, 4096), I32)] * 2),
    "search": (ops.search_pages, [((512, 4096), I32), ((4,), I32)]),
    "int8_matmul": (ops.int8_matmul, [((1024, 2048), I8), ((2048, 1024), I8)]),
    "attention_2k": (ops.flash_attention, [((8, 2048, 128), BF16)] * 3),
    "attention_32k_causal": (ops.flash_attention,
                             [((8, 32768, 128), BF16)] * 3),
    "attention_32k_full": (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=False),
        [((8, 32768, 128), BF16)] * 3),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _serving_step(step, batch, prompt, max_seq, one_chip, arch="qwen3-4b",
                  layers=None):
    """A prefill or decode step at published widths (``layers`` of them
    where given), caches donated as ``serve()`` and the benchmark donate
    them: (compiled, the caches' shapes)."""
    cfg = configs.get(arch)
    if layers:
        cfg = dataclasses.replace(cfg, n_layers=layers)
    shapes = cache_shapes(cfg, batch, max_seq)
    params, caches = _on(params_shapes(cfg), one_chip), _on(shapes, one_chip)
    if step == "prefill":
        fn, args = build_prefill_step(cfg), (
            params, caches, {"tokens": _sds((batch, prompt), I32, one_chip)})
    else:
        fn, args = build_serve_step(cfg), (
            params, caches, _sds((batch,), I32, one_chip),
            _sds((), I32, one_chip))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    return compiled, jax.tree_util.tree_leaves(shapes)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_qwen3_4b_serving_step_fits_one_v5e(step, one_chip):
    """The serving steps of chip_smoke.py at published widths: batch 8,
    1024-token prompts, a 1152-token cache donated to the step."""
    compiled, _ = _serving_step(step, 8, 1024, 1024 + 128, one_chip)
    assert _device_bytes(compiled) < TPU_V5E.hbm_bytes


# the shapes a copy or an asynchronous copy's start produces
_COPIED = re.compile(r"^\s*(?:ROOT )?%?\S+ = (.*?)[)}] copy(?:-start)?\(",
                     re.M)


def _copied_stacks(compiled, stacks):
    copied = " ".join(_COPIED.findall(compiled.as_text()))
    return [a for a in stacks if f"[{','.join(map(str, a.shape))}]" in copied]


@pytest.mark.parametrize("batch", [1, 4, 16])
def test_qwen3_4b_decode_keeps_the_kv_cache_in_place(batch, one_chip):
    """The benchmark's shapes (max_seq 1280) at the batch of each K/V
    order (1, 4 and the benchmark's 16): the decode step copies no K/V
    stack, and its temporaries are under 1% of the stacks (the sliced
    scan's were above 100%)."""
    compiled, stacks = _serving_step("decode", batch, 256, 1280, one_chip)
    assert not _copied_stacks(compiled, stacks)
    kv_bytes = sum(a.size * a.dtype.itemsize for a in stacks)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01 * kv_bytes


# (arch, layers, batch) where ``L.kv_carried`` holds: grouped and full
# heads 128 lanes wide, at batch 1 and at multiples of 8 short of 128
CARRIED = [("qwen2-vl-2b", None, 1), ("qwen2-vl-2b", None, 8),
           ("dbrx-132b", 2, 16), ("qwen3-4b", 2, 64), ("qwen3-4b", 2, 120),
           ("llama2-7b", 2, 1), ("llama2-7b", 2, 16)]


@pytest.mark.parametrize("arch, layers, batch", CARRIED,
                         ids=[f"{a}-{b}" for a, _, b in CARRIED])
def test_decode_keeps_carried_stacks_in_place(arch, layers, batch,
                                              one_chip):
    """Other architectures and batches than the benchmark's: the carried
    K/V stacks are not copied either."""
    compiled, stacks = _serving_step("decode", batch, 256, 1280, one_chip,
                                     arch, layers)
    assert L.kv_carried(configs.get(arch), batch)
    assert not _copied_stacks(compiled, stacks)


# where no order keeps a carried stack in place: 64-wide heads at
# serve()'s default batch and at 8, a batch with no tile of 8, and one that
# fills the 128 lanes
SLICED = [("tinyllama-1.1b", None, 4), ("stablelm-1.6b", None, 8),
          ("qwen3-4b", None, 12), ("qwen3-4b", 2, 128)]


@pytest.mark.parametrize("arch, layers, batch", SLICED,
                         ids=[f"{a}-{b}" for a, _, b in SLICED])
def test_decode_slices_where_carried_stacks_are_copied(arch, layers, batch,
                                                       one_chip, monkeypatch):
    """The cache is sliced per layer at these shapes because carrying it
    would copy the stacks: the premise of ``L.kv_order``'s rule."""
    assert not L.kv_carried(configs.get(arch), batch)
    monkeypatch.setattr(L, "kv_carried", lambda cfg, b: True)
    compiled, stacks = _serving_step("decode", batch, 256, 1280, one_chip,
                                     arch, layers)
    assert _copied_stacks(compiled, stacks)


def test_sliced_stablelm_decode_fits_where_carried_would_not(one_chip,
                                                            monkeypatch):
    """stablelm-1.6b at batch 20, max_seq 1280: sliced, the decode step
    fits one v5e; carried, the copies of its stacks overrun the chip."""
    compiled, _ = _serving_step("decode", 20, 256, 1280, one_chip,
                                "stablelm-1.6b")
    assert _device_bytes(compiled) < TPU_V5E.hbm_bytes
    monkeypatch.setattr(L, "kv_carried", lambda cfg, b: True)
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _serving_step("decode", 20, 256, 1280, one_chip, "stablelm-1.6b")


def test_stablelm_train_step_shards_over_2x2(topo):
    """Two stablelm-1.6b layers at full width, the train step train()
    jits for a (data=2, model=2) mesh of described chips: each chip holds
    a quarter of the state."""
    cfg = dataclasses.replace(configs.get("stablelm-1.6b"), n_layers=2)
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices[:4])
    step_fn, sh, batch_sh = jit_train_step(cfg, 3, 1e-3, mesh, (8, 1024))
    p_shapes = params_shapes(cfg)
    o_shapes = opt_shapes(cfg, p_shapes)
    place = lambda shapes, shard: jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, s), shapes, shard)
    batch = {k: _sds((8, 1024), I32, batch_sh) for k in ("tokens", "labels")}
    with on_mesh(mesh):
        compiled = step_fn.lower(place(p_shapes, sh["params"]),
                                 place(o_shapes, sh["opt"]), batch).compile()
    state = sum(a.size * a.dtype.itemsize for a in
                jax.tree_util.tree_leaves((p_shapes, o_shapes)))
    args = compiled.memory_analysis().argument_size_in_bytes
    assert abs(args / (state / 4) - 1) < 0.05
    assert _device_bytes(compiled) < TPU_V5E.hbm_bytes
    assert "all-reduce" in compiled.as_text()
