"""Decode attention over the stacked K/V cache, per layer, on a TPU.

The shapes are the ``qwen3-4b.decode`` cell's: a [36, 1280, 8, 16, 128]
bf16 K and V stack (``models/layers.kv_order``'s ``shbd``), 32 query heads
of 128 over 8 KV heads, batch 16. For each write position ``index`` and
each block size, the Pallas kernel (``kernels/attention.
decode_gqa_attention``) runs over all 36 layers in one jitted scan, and so
does the jnp path it replaces (``models/layers._cached_attention``: the
layer sliced out, two einsums over all positions). A row gives the time a
layer, the bytes of the filled prefix (positions 0..index of K and V) over
that time, its share of the chip's HBM bandwidth (``chipbench/peaks.json``),
and the largest gap to the jnp path's output.

    python -m benchmarks.decode_attention_bench [--reps 20]

Refuses to run without a TPU (exit 2).
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.kernels.attention import decode_gqa_attention
from repro.models.layers import _cached_attention

LAYERS, SEQ, HKV, BATCH, HEADS, DH = 36, 1280, 8, 16, 32, 128
INDICES = (767, 1279)
BLOCKS = (128, 256, 512)


def _over_layers(attend):
    """jit: q [B, H, dh] against every layer of the stacks at ``index``;
    the sum of the outputs keeps each layer's call."""
    def run(q, k, v, index):
        def body(acc, layer):
            return acc + attend(q, k, v, layer, index).astype(
                jnp.float32), None
        zero = jnp.zeros(q.shape, jnp.float32)
        return jax.lax.scan(body, zero, jnp.arange(LAYERS))[0]
    return jax.jit(run)


def _seconds(fn, args, reps):
    jax.block_until_ready(fn(*args))            # compile and warm up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU (found {dev.platform}): nothing to measure",
              file=sys.stderr)
        return 2
    peaks = json.loads((Path(__file__).parents[1] / "chipbench"
                        / "peaks.json").read_text())[dev.device_kind]
    bandwidth = peaks["hbm_bytes_per_s"]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    stack = (LAYERS, SEQ, HKV, BATCH, DH)
    q = jax.random.normal(ks[0], (BATCH, HEADS, DH), jnp.bfloat16)
    k = jax.random.normal(ks[1], stack, jnp.bfloat16)
    v = jax.random.normal(ks[2], stack, jnp.bfloat16)

    def jnp_path(q, k, v, layer, index):
        return _cached_attention(q[:, None], k, v, layer, index, "shbd")[:, 0]

    paths = {"jnp": jnp_path}
    for block in BLOCKS:
        paths[f"kernel_{block}"] = (
            lambda q, k, v, layer, index, block=block:
            decode_gqa_attention(q, k, v, layer, index, block=block))
    print(f"# {dev.platform} {dev.device_kind}, one layer of K/V "
          f"[{SEQ}, {HKV}, {BATCH}, {DH}] bf16, q [{BATCH}, {HEADS}, {DH}]")
    rows = []
    for index in INDICES:
        idx = jnp.int32(index)
        prefix = 2 * (index + 1) * HKV * BATCH * DH * k.dtype.itemsize
        want = _over_layers(jnp_path)(q, k, v, idx)
        for name, attend in paths.items():
            fn = _over_layers(attend)
            per_layer = _seconds(fn, (q, k, v, idx), args.reps) / LAYERS
            gap = float(jnp.max(jnp.abs(fn(q, k, v, idx) - want)))
            row = {"path": name, "index": index,
                   "us_per_layer": round(per_layer * 1e6, 2),
                   "prefix_bytes": prefix,
                   "prefix_gb_per_s": round(prefix / per_layer / 1e9, 1),
                   "hbm_share": round(prefix / per_layer / bandwidth, 4),
                   "max_gap_to_jnp": gap}
            rows.append(row)
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
