"""Model FLOPs of one training step of a dense decoder, from its shapes.

Forward: every weight matrix, the unembedding included, times each token
(2 FLOPs a multiply-add; the embedding is a gather; biases and norms are
left out), and attention's two products over the causal half of each
sequence, ``2 * L * B * S^2 * H * dh``. The backward pass costs twice the
forward, so a step is three forwards. Layers recomputed in the backward
pass (remat) are not counted: a program that recomputes is below 100% of
the chip's peak for that work.
"""
from __future__ import annotations

from typing import Mapping

from chipbench import flops as F


def train_step_flops(c: Mapping, batch: int, seq: int) -> float:
    tokens = batch * seq
    attn = 2.0 * c["n_layers"] * batch * seq * seq \
        * c["n_heads"] * F.head_dim(c)
    return 3.0 * (2.0 * F.matmul_params_per_token(c) * tokens + attn)
