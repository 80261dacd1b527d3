"""Model assembly: any ArchConfig -> init / loss / prefill / decode.

Layers with identical block kinds are grouped into *segments*; each segment
stacks its parameters along a leading layer axis and executes under
``jax.lax.scan`` — HLO size is O(#segments), not O(depth), which keeps the
236B-parameter dry-run compiles fast.  Heterogeneous patterns (zamba2's
mamba blocks + shared attention, xLSTM's mlstm/slstm alternation) become
short segment lists.  ``jax.checkpoint`` wraps the block body when
``cfg.remat`` (activation rematerialization for training).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models import layers as L
from repro.models import ssm as S

Params = Dict[str, Any]


# -- pattern segmentation ------------------------------------------------------

def segments_of(cfg: ArchConfig) -> List[Tuple[str, int]]:
    segs: List[Tuple[str, int]] = []
    for kind in cfg.pattern:
        if segs and segs[-1][0] == kind:
            segs[-1] = (kind, segs[-1][1] + 1)
        else:
            segs.append((kind, 1))
    return segs


# -- per-block init -------------------------------------------------------------

def _attn_init(key, cfg, dtype):
    if cfg.mla:
        return L.mla_init(key, cfg, dtype)
    return L.gqa_init(key, cfg, dtype)


def _block_init(kind: str, key, cfg: ArchConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    if kind == "attn":
        return {"ln1": L.rmsnorm_init(cfg.d_model, dtype),
                "attn": _attn_init(ks[0], cfg, dtype),
                "ln2": L.rmsnorm_init(cfg.d_model, dtype),
                "mlp": L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dtype)}
    if kind == "moe":
        return {"ln1": L.rmsnorm_init(cfg.d_model, dtype),
                "attn": _attn_init(ks[0], cfg, dtype),
                "ln2": L.rmsnorm_init(cfg.d_model, dtype),
                "moe": L.moe_init(ks[1], cfg, dtype)}
    if kind == "xdec":   # encoder-decoder decoder block (self + cross + mlp)
        return {"ln1": L.rmsnorm_init(cfg.d_model, dtype),
                "attn": L.gqa_init(ks[0], cfg, dtype),
                "lnx": L.rmsnorm_init(cfg.d_model, dtype),
                "xattn": L.gqa_init(ks[1], cfg, dtype),
                "ln2": L.rmsnorm_init(cfg.d_model, dtype),
                "mlp": L.mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype)}
    if kind == "mamba":
        return S.mamba_init(key, cfg, dtype)
    if kind == "mlstm":
        return S.mlstm_init(key, cfg, dtype)
    if kind == "slstm":
        return S.slstm_init(key, cfg, dtype)
    raise ValueError(kind)


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ArchConfig, key) -> Params:
    """Random weights from ``key``, as one jitted program: each leaf's
    float32 draw is scaled, cast and freed inside it, so at most one draw is
    resident beside the finished ``cfg.dtype`` params (op-by-op dispatch
    held a draw and its scaled copy)."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    p: Params = {
        "emb": L.dense_init(keys[0], cfg.vocab, cfg.d_model, dtype, scale=0.02),
        "ln_f": L.rmsnorm_init(cfg.d_model, dtype),
        "segments": [],
    }
    if not cfg.tie_embeddings:
        p["unemb"] = L.dense_init(keys[1], cfg.d_model, cfg.vocab, dtype)
    seg_keys = jax.random.split(keys[2], max(1, len(segments_of(cfg))))
    for (kind, count), sk in zip(segments_of(cfg), seg_keys):
        if kind == "sattn":   # shared block: parameters stored once
            p["segments"].append(None)
            continue
        stacked = jax.vmap(
            lambda k: _block_init(kind, k, cfg, dtype))(
                jax.random.split(sk, count))
        p["segments"].append(stacked)
    if cfg.shared_attn_every:
        p["shared_attn"] = _block_init("attn", keys[3], cfg, dtype)
    if cfg.enc_layers:
        enc = jax.vmap(
            lambda k: _block_init("attn", k, cfg, dtype))(
                jax.random.split(keys[4], cfg.enc_layers))
        p["encoder"] = enc
    return p


# -- per-block apply -------------------------------------------------------------

def _attention(p, cfg, x, positions, cache, pos3):
    with jax.named_scope("attn"):
        if cfg.mla:
            return L.mla_attention(p, cfg, x, positions, cache)
        return L.gqa_attention(p, cfg, x, positions, cache, pos3=pos3)


def _norm(cfg: ArchConfig, x, g):
    with jax.named_scope("norm"):
        return L.rmsnorm(x, g, cfg.norm_eps)


def block_apply(kind: str, cfg: ArchConfig, p: Params, x, positions,
                cache=None, pos3=None, enc_out=None):
    """Returns (x, new_cache).  Its ops are named ``block/norm``,
    ``block/attn`` (``attn/kv_write`` for the cache writes),
    ``block/mlp`` or ``block/moe``, ``block/mixer`` (the SSM blocks), and
    ``block`` alone for the residual adds (``repro.models.scopes``)."""
    with jax.named_scope("block"):
        if kind in ("attn", "moe", "xdec"):
            h, new_cache = _attention(p["attn"], cfg,
                                      _norm(cfg, x, p["ln1"]),
                                      positions, cache, pos3)
            x = x + h
            if kind == "xdec" and enc_out is not None:
                xn = _norm(cfg, x, p["lnx"])
                with jax.named_scope("attn"):
                    h, _ = L.gqa_attention(p["xattn"], cfg, xn, positions,
                                           None, kv_source=enc_out)
                x = x + h
            xin = _norm(cfg, x, p["ln2"])
            if kind == "moe":
                with jax.named_scope("moe"):
                    h = L.moe_apply(p["moe"], cfg, xin)
            else:
                with jax.named_scope("mlp"):
                    h = L.mlp_apply(p["mlp"], xin)
            return x + h, new_cache
        with jax.named_scope("mixer"):
            if kind == "mamba":
                return S.mamba_apply(p, cfg, x, cache)
            if kind == "mlstm":
                return S.mlstm_apply(p, cfg, x, cache)
            if kind == "slstm":
                return S.slstm_apply(p, cfg, x, cache)
    raise ValueError(kind)


# -- caches / states ---------------------------------------------------------

def _block_cache(kind: str, cfg: ArchConfig, batch: int, max_seq: int):
    dtype = jnp.dtype(cfg.dtype)
    if kind in ("attn", "moe", "xdec"):
        if cfg.mla:
            return {"latent": jnp.zeros((batch, max_seq, cfg.kv_lora_rank),
                                        dtype),
                    "k_rope": jnp.zeros((batch, max_seq, cfg.rope_head_dim),
                                        dtype)}
        size = {"b": batch, "s": max_seq, "h": cfg.n_kv_heads,
                "d": cfg.head_dim}
        shape = tuple(size[a] for a in L.kv_order(cfg, batch))
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kind == "mamba":
        return S.mamba_state(cfg, batch)
    if kind == "mlstm":
        return S.mlstm_state(cfg, batch)
    if kind == "slstm":
        return S.slstm_state(cfg, batch)
    raise ValueError(kind)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def init_cache(cfg: ArchConfig, batch: int, max_seq: int) -> List[Any]:
    """Empty caches and states for ``batch`` sequences of ``max_seq``
    positions, each segment's stacked over its layers. One jitted program
    writes each stack once: op by op, a stack's layer, its broadcast and
    its copy were resident together (1.6 GB above the caches for
    qwen3-4b at batch 16, the serving process's peak)."""
    caches = []
    for kind, count in segments_of(cfg):
        one = _block_cache("attn" if kind == "sattn" else kind,
                           cfg, batch, max_seq)
        caches.append(jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (count,) + a.shape), one))
    return caches


# -- forward -------------------------------------------------------------------

def _with_index(cache, idx, layer=0):
    """What one layer's attention takes: GQA ``k``/``v`` stacks with the
    write position and the layer, MLA caches with the write position."""
    if cache is None:
        return None
    if "k" in cache:
        return dict(cache, index=idx, layer=layer)
    if "latent" in cache:
        return dict(cache, index=idx)
    return cache


def _strip_index(cache):
    if cache is None:
        return None
    return {k: v for k, v in cache.items() if k != "index"}


def forward(cfg: ArchConfig, params: Params, x, positions,
            caches: Optional[List] = None, index=None, pos3=None,
            enc_out=None):
    """Backbone forward. ``x`` [B,S,D] embeddings; returns (h, new_caches).

    Each segment runs under the ``layers`` scope: the scan's slicing of the
    stacked params (and of the caches it does not carry, ``_segment``) is
    named there and outside ``block``."""
    new_caches: List[Any] = []
    for si, (seg_params, (kind, _)) in enumerate(
            zip(params["segments"], segments_of(cfg))):
        seg_cache = caches[si] if caches is not None else None
        with jax.named_scope("layers"):
            x, nc = _segment(cfg, kind, params, seg_params, x, positions,
                             seg_cache, index, pos3, enc_out)
        new_caches.append(nc)
    return x, new_caches


def _segment(cfg: ArchConfig, kind: str, params: Params, seg_params, x,
             positions, seg_cache, index, pos3, enc_out):
    """One segment of ``forward``: (x, its new caches or None).

    Where ``L.kv_carried`` holds for the shapes, GQA ``k``/``v`` stacks
    ride in the scan's carry, so each layer writes its new rows into the
    donated buffers in place; the layer's index comes in as the scan's
    input. Other caches and states (MLA latents, SSM and xLSTM states, and
    K/V at other shapes) are sliced per layer as the scan's inputs and
    stacked anew as its outputs."""
    if kind == "sattn":
        x, nc = block_apply("attn", cfg, params["shared_attn"], x,
                            positions, _with_index(seg_cache, index), pos3,
                            enc_out)
        return x, nc

    if seg_cache is None:
        def run_block(p_l, xh):
            out, _ = block_apply(kind, cfg, p_l, xh, positions,
                                 None, pos3, enc_out)
            return out
        if cfg.remat:
            run_block = jax.checkpoint(run_block)
        x, _ = jax.lax.scan(
            lambda c, p_l: (run_block(p_l, c), None), x, seg_params)
        return x, None

    kv = "k" in seg_cache
    if kv and L.kv_carried(cfg, x.shape[0]):
        def carried(carry, xs):
            xh, c = carry
            p_l, layer = xs
            return block_apply(kind, cfg, p_l, xh, positions,
                               _with_index(c, index, layer), pos3,
                               enc_out), None
        count = seg_cache["k"].shape[0]
        (x, nc), _ = jax.lax.scan(carried, (x, seg_cache),
                                  (seg_params, jnp.arange(count)))
        return x, nc

    # gqa_attention takes a stack: here of the one layer sliced out
    add_layer = lambda c: jax.tree_util.tree_map(lambda a: a[None], c)
    drop_layer = lambda c: jax.tree_util.tree_map(lambda a: a[0], c)

    def body(carry, xs):
        p_l, c_l = xs
        out, nc = block_apply(kind, cfg, p_l, carry, positions,
                              _with_index(add_layer(c_l) if kv else c_l,
                                          index), pos3, enc_out)
        nc = _strip_index(nc)
        return out, drop_layer(nc) if kv else nc
    return jax.lax.scan(body, x, (seg_params, seg_cache))


def encode(cfg: ArchConfig, params: Params, feats, positions):
    """Bidirectional encoder over (stubbed) frontend features [B,S,D],
    named as ``forward``'s layers are."""
    def body(x, p_l):
        with jax.named_scope("block"):
            xn = _norm(cfg, x, p_l["ln1"])
            with jax.named_scope("attn"):
                h, _ = L.gqa_attention(p_l["attn"], cfg, xn, positions, None,
                                       causal=False)
            x = x + h
            xn = _norm(cfg, x, p_l["ln2"])
            with jax.named_scope("mlp"):
                h = L.mlp_apply(p_l["mlp"], xn)
            return x + h, None
    with jax.named_scope("layers"):
        out, _ = jax.lax.scan(body, feats, params["encoder"])
    return out


def embed(cfg: ArchConfig, params: Params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(params["emb"], tokens, axis=0)


def logits_of(cfg: ArchConfig, params: Params, h, pad_vocab: bool = False):
    """Final projection.  ``pad_vocab`` (perf iteration M2): odd vocabularies
    (e.g. minicpm's 122753) cannot shard over a 16-way model axis, leaving
    the [B,S,V] fp32 logits replicated along it; padding the output dim to a
    512-multiple makes the largest activation of the training step
    model-shardable.  Padded columns are -inf so logsumexp is unchanged.
    Named ``head``, with the final norm."""
    with jax.named_scope("head"):
        h = L.rmsnorm(h, params["ln_f"], cfg.norm_eps)
        unemb = params["emb"].T if cfg.tie_embeddings else params["unemb"]
        pad = (-cfg.vocab) % 512 if pad_vocab else 0
        if pad:
            unemb = jnp.pad(unemb, ((0, 0), (0, pad)))
        logits = h @ unemb
        if pad:
            neg = jnp.full((pad,), -1e30, logits.dtype)
            logits = logits.at[..., cfg.vocab:].set(neg)
        return logits


# -- task-level functions --------------------------------------------------------

def lm_loss(cfg: ArchConfig, params: Params, tokens, labels,
            extra_embeds=None, pos3=None, enc_feats=None):
    """Causal-LM cross entropy.  ``extra_embeds`` (VLM patch stubs) are
    prepended; ``enc_feats`` (audio stubs) drive the encoder of enc-dec
    architectures."""
    x = embed(cfg, params, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    x = L.shard_tokens(x)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    enc_out = None
    if cfg.enc_layers and enc_feats is not None:
        enc_pos = jnp.broadcast_to(jnp.arange(enc_feats.shape[1]),
                                   enc_feats.shape[:2])
        enc_out = encode(cfg, params, enc_feats.astype(x.dtype), enc_pos)
    h, _ = forward(cfg, params, x, positions, pos3=pos3, enc_out=enc_out)
    logits = logits_of(cfg, params, h, pad_vocab=bool(L.model_axis()))
    if extra_embeds is not None:
        logits = logits[:, extra_embeds.shape[1]:]
    logits = logits.astype(jnp.float32)
    # logits shard vocab over the model axis (the [B,S,V] fp32 tensor is by
    # far the largest activation; see EXPERIMENTS.md §Perf)
    logits = L.shard_tokens(logits)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def prefill(cfg: ArchConfig, params: Params, tokens, caches,
            extra_embeds=None, pos3=None, enc_feats=None):
    """Run the prompt through the model, filling caches; returns
    (last-token logits, caches)."""
    x = embed(cfg, params, tokens)
    if extra_embeds is not None:
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    x = L.shard_tokens(x)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    enc_out = None
    if cfg.enc_layers and enc_feats is not None:
        enc_pos = jnp.broadcast_to(jnp.arange(enc_feats.shape[1]),
                                   enc_feats.shape[:2])
        enc_out = encode(cfg, params, enc_feats.astype(x.dtype), enc_pos)
    h, caches = forward(cfg, params, x, positions, caches=caches, index=0,
                        pos3=pos3, enc_out=enc_out)
    return logits_of(cfg, params, h[:, -1:]), caches


def decode_step(cfg: ArchConfig, params: Params, token, index, caches,
                enc_out=None):
    """One decode step: ``token`` [B] at position ``index`` (scalar)."""
    x = embed(cfg, params, token[:, None])
    b = x.shape[0]
    positions = jnp.full((b, 1), index, jnp.int32)
    pos3 = (jnp.broadcast_to(positions, (3, b, 1))
            if cfg.mrope else None)
    h, caches = forward(cfg, params, x, positions, caches=caches,
                        index=index, pos3=pos3, enc_out=enc_out)
    return logits_of(cfg, params, h)[:, 0], caches
