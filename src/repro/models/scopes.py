"""Which part of the model each instruction of a compiled step belongs to.

The model names its parts with ``jax.named_scope``: ``embed``, ``layers``
(each segment's scan), ``block`` (one layer), and inside it ``norm``,
``attn`` (``kv_write`` around the cache writes), ``mlp`` or ``moe``, and
``mixer`` (the SSM blocks); ``head`` (final norm and logits); and
``optimizer`` around a train step's AdamW update (``optim/adamw``).  The
names reach the optimized HLO as each instruction's ``op_name`` metadata:
``jit(serve_step)/layers/while/body/closed_call/block/attn/dot_general``,
and under autodiff wrapped as ``transpose(jvp(block))``.  A profiler trace
names each device op by its HLO instruction, so ``op_scopes`` turns a
trace's per-op times into time per part of the model.

A fusion is attributed by its own ``op_name``, which XLA takes from the
fusion's root op: a fusion that ends in a residual add also holds the
attention or MLP ops fused into it and counts as ``norm_residual``.
Instructions that XLA made with no metadata (copies between loop
iterations, most parameters and tuples) are ``unscoped``.
"""
from __future__ import annotations

import re
from typing import Dict, FrozenSet

# disjoint buckets that together take every instruction
BUCKETS = ("attention", "kv_write", "mlp", "norm_residual", "layer_scan",
           "head", "optimizer", "unscoped")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^([\w\-]+)\((.*)\)$")


def _scope(component: str) -> str:
    """'transpose(jvp(block))' -> 'block'; 'jit(main)' -> '' (a jitted
    function's name, not a scope)."""
    m = _WRAPPED.match(component)
    while m:
        if m.group(1) in ("jit", "pjit"):
            return ""
        component = m.group(2)
        m = _WRAPPED.match(component)
    return component


def scopes_of(op_name: str) -> FrozenSet[str]:
    """The scope names in an ``op_name``; its last part, the primitive (or
    an argument's name), is not a scope."""
    return frozenset(_scope(c) for c in op_name.split("/")[:-1]) - {""}


def bucket(op_name: str) -> str:
    """The bucket of one ``op_name`` (``BUCKETS``)."""
    s = scopes_of(op_name)
    if "kv_write" in s:
        return "kv_write"
    if s & {"attn", "mixer"}:
        return "attention"
    if s & {"mlp", "moe"}:
        return "mlp"
    if "block" in s:
        return "norm_residual"
    if "layers" in s:
        return "layer_scan"
    if s & {"embed", "head"}:
        return "head"
    if "optimizer" in s:
        return "optimizer"
    return "unscoped"


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: bucket} for every instruction of every
    computation of an optimized HLO module (``Compiled.as_text()``), while
    bodies and fused computations included."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            name = _OP_NAME.search(line)
            out[m.group(1)] = bucket(name.group(1)) if name else "unscoped"
    return out
