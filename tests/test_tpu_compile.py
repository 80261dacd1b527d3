"""Compile rehearsals for a described (not attached) TPU v5e.

The TPU compiler ships with libtpu, so the programs the chip runs can be
compiled here without a chip: every kept Pallas kernel at real sizes (the
32k-token attention and the int8 cases included), qwen3-4b prefill and
decode at published widths, and a stablelm-1.6b train step sharded over a
2x2 mesh.  Nothing executes; these catch what interpret mode cannot —
Mosaic lowering refusals, VMEM and HBM overruns, unpartitionable programs.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and every test worker imports this file.
"""
import dataclasses
import os

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


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_qwen3_4b_serving_step_fits_one_v5e(step, one_chip):
    """The serving steps of chip_smoke.py at published widths: batch 8,
    1024-token prompts, a 1152-token cache donated to the step."""
    cfg = configs.get("qwen3-4b")
    batch, prompt, max_seq = 8, 1024, 1024 + 128
    params = _on(params_shapes(cfg), one_chip)
    caches = _on(cache_shapes(cfg, batch, max_seq), one_chip)
    if step == "prefill":
        fn = build_prefill_step(cfg)
        args = (params, caches,
                {"tokens": _sds((batch, prompt), I32, one_chip)})
    else:
        fn = build_serve_step(cfg)
        args = (params, caches, _sds((batch,), I32, one_chip),
                _sds((), I32, one_chip))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    assert _device_bytes(compiled) < TPU_V5E.hbm_bytes


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
