"""Named scopes through the model step: every instruction of a compiled
step falls in one bucket of ``repro.models.scopes``, and the scopes change
no computation (the optimized HLO without its metadata is the same)."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch.specs import cache_shapes, params_shapes
from repro.launch.steps import build_prefill_step, build_serve_step
from repro.models import model as M
from repro.models import scopes as SC

I32 = jnp.int32
BATCH, PROMPT, MAX_SEQ = 2, 8, 16


@pytest.fixture(autouse=True)
def fresh_compiles():
    """The persistent compile cache's key leaves out metadata: it could
    hand one compile the executable of another with other scopes."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _cfg(arch="qwen3-4b"):
    return dataclasses.replace(configs.get(arch).reduced(), n_layers=2)


def compiled_text(step: str, cfg=None) -> str:
    """Optimized HLO of a reduced step, compiled as the benchmark's
    serving loop compiles it (caches donated)."""
    cfg = cfg or _cfg()
    params = params_shapes(cfg)
    caches = cache_shapes(cfg, BATCH, MAX_SEQ)
    if step == "prefill":
        fn, args = build_prefill_step(cfg), (
            params, caches,
            {"tokens": jax.ShapeDtypeStruct((BATCH, PROMPT), I32)})
    else:
        fn, args = build_serve_step(cfg), (
            params, caches, jax.ShapeDtypeStruct((BATCH,), I32),
            jax.ShapeDtypeStruct((), I32))
    return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()


def instruction_names(hlo_text: str):
    return re.findall(r"^\s*(?:ROOT )?%?(\S+) = ", hlo_text, re.M)


_METADATA = re.compile(r', metadata=\{(?:[^}"]|"[^"]*")*\}')
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")


def strip_metadata(hlo_text: str) -> str:
    """The HLO without ``metadata={...}`` and the stack-frame tables that
    its ``stack_frame_id``s index."""
    out, skipping = [], False
    for line in hlo_text.splitlines():
        if line in _DEBUG_TABLES:
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            out.append(_METADATA.sub("", line))
    return "\n".join(out)


@pytest.mark.parametrize("step", ["serve", "prefill"])
def test_every_instruction_falls_in_one_bucket(step):
    text = compiled_text(step)
    names = instruction_names(text)
    assert len(names) == len(set(names))
    m = SC.op_scopes(text)
    assert set(m) == set(names)
    assert set(m.values()) <= set(SC.BUCKETS)
    if step == "serve":
        assert set(m.values()) >= {"attention", "kv_write", "mlp",
                                   "norm_residual", "layer_scan", "head"}


@pytest.mark.parametrize("step", ["serve", "prefill"])
def test_scopes_change_no_computation(step, monkeypatch):
    scoped = compiled_text(step)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled_text(step)
    assert "block/attn" in scoped and "block/attn" not in plain
    assert strip_metadata(scoped) == strip_metadata(plain)


@pytest.mark.parametrize("op_name, bucket", [
    ("jit(serve_step)/jit(main)/layers/while/body/closed_call/block/attn/"
     "kv_write/dynamic_update_slice", "kv_write"),
    ("jit(serve_step)/layers/while/body/closed_call/block/attn/dot_general",
     "attention"),
    ("jit(serve_step)/layers/while/body/closed_call/block/mixer/while/body/"
     "mul", "attention"),
    ("jit(f)/layers/while/body/closed_call/block/moe/dot_general", "mlp"),
    ("jit(f)/transpose(jvp(layers))/while/body/closed_call/"
     "transpose(jvp(block))/transpose(jvp(mlp))/dot_general", "mlp"),
    ("jit(serve_step)/layers/while/body/closed_call/block/norm/rsqrt",
     "norm_residual"),
    ("jit(serve_step)/layers/while/body/closed_call/block/add",
     "norm_residual"),
    ("jit(serve_step)/layers/while/body/dynamic_slice", "layer_scan"),
    ("jit(serve_step)/layers/while", "layer_scan"),
    ("jit(serve_step)/embed/jit(_take)", "head"),
    ("jit(serve_step)/head/dot_general", "head"),
    ("jit(train_step)/jvp(jit(main))/log", "unscoped"),
    # a jitted function's name and the primitive are not scopes
    ("jit(block)/jit(mlp)/add", "unscoped"),
    ("params['segments'][0]['attn']['wq']", "unscoped"),
    ("", "unscoped"),
])
def test_bucket_of_an_op_name(op_name, bucket):
    assert SC.bucket(op_name) == bucket


def test_instructions_without_metadata_are_unscoped():
    text = ("ENTRY %main.5 (x.1: f32[4]) -> f32[4] {\n"
            "  %x.1 = f32[4]{0} parameter(0), metadata={op_name=\"x\"}\n"
            "  %copy.2 = f32[4]{0} copy(%x.1)\n"
            "  ROOT %add_fusion.3 = f32[4]{0} fusion(%copy.2), kind=kLoop, "
            "calls=%f, metadata={op_name=\"jit(f)/layers/while/body/"
            "closed_call/block/add\" stack_frame_id=3}\n}\n")
    assert SC.op_scopes(text) == {"x.1": "unscoped", "copy.2": "unscoped",
                                  "add_fusion.3": "norm_residual"}


def test_the_backward_pass_keeps_its_scopes():
    cfg = _cfg()
    params = params_shapes(cfg)
    tokens = jax.ShapeDtypeStruct((BATCH, PROMPT), I32)
    grad = jax.jit(jax.grad(lambda p, t: M.lm_loss(cfg, p, t, t)))
    text = grad.lower(params, tokens).compile().as_text()
    m = SC.op_scopes(text)
    backward = {m[n] for n, op in re.findall(
        r"^\s*(?:ROOT )?%?(\S+) = .*op_name=\"([^\"]*)\"", text, re.M)
        if "transpose(" in op}
    assert backward >= {"attention", "mlp", "norm_residual", "head"}


@pytest.mark.parametrize("arch, pattern", [
    ("deepseek-v2-236b", None),                    # MLA attention, MoE
    ("zamba2-1.2b", ("mamba", "mamba", "sattn")),  # SSM, shared attention
])
def test_other_blocks_are_scoped(arch, pattern):
    cfg = configs.get(arch).reduced()
    if pattern:
        cfg = dataclasses.replace(cfg, block_pattern=pattern,
                                  n_layers=len(pattern))
    m = SC.op_scopes(compiled_text("serve", cfg))
    assert set(m.values()) >= {"attention", "kv_write", "mlp",
                               "norm_residual", "layer_scan", "head"}


def test_the_train_steps_adamw_update_lands_in_optimizer():
    """The AdamW update (and its clip's global norm) is its own bucket;
    the forward and backward passes keep theirs."""
    from repro.launch.specs import opt_shapes
    from repro.launch.steps import build_train_step
    cfg = _cfg("stablelm-1.6b")
    params = params_shapes(cfg)
    batch = {k: jax.ShapeDtypeStruct((BATCH, PROMPT), I32)
             for k in ("tokens", "labels")}
    text = jax.jit(build_train_step(cfg), donate_argnums=(0, 1)).lower(
        params, opt_shapes(cfg, params), batch).compile().as_text()
    m = SC.op_scopes(text)
    assert set(m.values()) >= {"attention", "mlp", "norm_residual", "head",
                               "optimizer"}
    scoped = re.findall(r"^\s*(?:ROOT )?%?(\S+) = .*op_name=\"([^\"]*)\"",
                        text, re.M)
    adam = [n for n, op in scoped if "/optimizer/" in op]
    assert adam and all(m[n] == "optimizer" for n in adam)
    # the update's square root of the second moment is the optimizer's
    assert any(m[n] == "optimizer" for n, op in scoped
               if op.endswith("/sqrt"))


def test_decode_buckets_are_those_of_before_the_optimizer_bucket():
    m = SC.op_scopes(compiled_text("serve"))
    assert set(m.values()) == {"attention", "kv_write", "mlp",
                               "norm_residual", "layer_scan", "head",
                               "unscoped"}
