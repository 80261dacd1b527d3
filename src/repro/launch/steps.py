"""Step functions: train_step / prefill_step / serve_step per architecture.

These are the functions the drivers and the benchmark jit.  The train
step is a full optimization step (loss, backward, AdamW with the arch's
schedule); the serve step is one decode iteration against the KV cache /
recurrent state.
"""
from __future__ import annotations

from typing import Callable

import jax

from repro.models import model as M
from repro.models.config import ArchConfig
from repro.optim import adamw_update, make_schedule


def build_train_step(cfg: ArchConfig, total_steps: int = 10_000,
                     base_lr: float = 3e-4) -> Callable:
    """Full optimization step: loss and gradients of the whole batch,
    then AdamW at the schedule's learning rate."""
    schedule = make_schedule(cfg.schedule, base_lr, total_steps)

    def loss_of(p, batch):
        return M.lm_loss(
            cfg, p, batch["tokens"], batch["labels"],
            extra_embeds=batch.get("extra_embeds"),
            pos3=batch.get("pos3"),
            enc_feats=batch.get("enc_feats"))

    def train_step(params, opt_state, batch):
        step = opt_state.step
        loss, grads = jax.value_and_grad(loss_of)(params, batch)
        lr = schedule(step)
        new_params, new_state, metrics = adamw_update(
            params, grads, opt_state, lr)
        metrics = dict(metrics, loss=loss, lr=lr)
        return new_params, new_state, metrics

    return train_step


def build_prefill_step(cfg: ArchConfig) -> Callable:
    def prefill_step(params, caches, batch):
        return M.prefill(
            cfg, params, batch["tokens"], caches,
            extra_embeds=batch.get("extra_embeds"),
            pos3=batch.get("pos3"),
            enc_feats=batch.get("enc_feats"))
    return prefill_step


def build_serve_step(cfg: ArchConfig) -> Callable:
    """One decode step: new token against a filled cache at ``index``."""
    def serve_step(params, caches, token, index, enc_out=None):
        return M.decode_step(cfg, params, token, index, caches,
                             enc_out=enc_out)
    return serve_step
