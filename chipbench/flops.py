"""Operations and bytes that a dense GQA decoder needs, from its shapes alone.

Every count here is a function of the configuration file's ``arch_config``
(the repo's ``ArchConfig`` field names), never of how the program computes:
a program that reads or computes more than these counts is below 100% of
its roofline, and no correct program can exceed it.
"""
from __future__ import annotations

from typing import Iterable, Mapping

BF16_BYTES = 2


def head_dim(c: Mapping) -> int:
    return c.get("d_head") or c["d_model"] // c["n_heads"]


def layer_matmul_params(c: Mapping) -> int:
    """Weights of one block that take part in a matrix product."""
    d, dh, f = c["d_model"], head_dim(c), c["d_ff"]
    attn = d * c["n_heads"] * dh * 2 + d * c["n_kv_heads"] * dh * 2
    return attn + 3 * d * f


def param_count(c: Mapping) -> int:
    """Every parameter: embeddings, blocks with their norms, final norm."""
    d, v, dh = c["d_model"], c["vocab"], head_dim(c)
    norms = 2 * d + (2 * dh if c.get("qk_norm") else 0)
    emb = v * d * (1 if c.get("tie_embeddings", True) else 2)
    return emb + c["n_layers"] * (layer_matmul_params(c) + norms) + d


def weight_bytes(c: Mapping) -> int:
    return param_count(c) * BF16_BYTES


def matmul_params_per_token(c: Mapping) -> int:
    """Weights each token multiplies by: every block and the unembedding
    (the embedding is a gather)."""
    return c["n_layers"] * layer_matmul_params(c) + c["d_model"] * c["vocab"]


def kv_bytes_per_token(c: Mapping) -> int:
    """K and V of one position over all layers, in bf16."""
    return c["n_layers"] * 2 * c["n_kv_heads"] * head_dim(c) * BF16_BYTES


def decode_step_flops(c: Mapping, contexts: Iterable[int]) -> float:
    """One decode step for a batch whose sequences attend over ``contexts``
    positions each (the new token included)."""
    per_pos = 4 * c["n_heads"] * head_dim(c) * c["n_layers"]  # QK and PV
    ctx = list(contexts)
    return 2.0 * matmul_params_per_token(c) * len(ctx) + per_pos * sum(ctx)


def decode_step_bytes(c: Mapping, contexts: Iterable[int]) -> float:
    """Weights once, plus the K/V of the positions each sequence attends."""
    return float(weight_bytes(c) + kv_bytes_per_token(c) * sum(contexts))


def decode_step_bound_s(c: Mapping, contexts, peaks: Mapping) -> float:
    """Least time one decode step could take on one chip."""
    ctx = list(contexts)
    return max(decode_step_flops(c, ctx) / peaks["bf16_flops"],
               decode_step_bytes(c, ctx) / peaks["hbm_bytes_per_s"])

