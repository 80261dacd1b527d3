"""Chip smoke test: the model stack end to end on TPU, every result checked.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four chips (one 2x2 host)

One chip: qwen3-4b at its published size answers 16 requests through
``repro.launch.serve.serve`` (batch 8, 1024-token prompts, 128 new tokens,
random weights from ``--seed``); cached decoding is checked against a
cache-free forward; every Pallas kernel runs compiled against its
``repro.kernels.ref`` oracle.

Four chips: stablelm-1.6b training through ``repro.launch.train.train``.
Two layers at full width take one step on one chip and one on a
(data=2, model=2) mesh, which must agree; then the full 24 layers take 3
steps on the mesh, which must stay finite and leave each chip a quarter of
the training state.

Exits non-zero, before printing a result, when JAX finds no TPU or any
check fails.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.launch.train import train  # noqa: E402
from repro.models import model as M  # noqa: E402

SERVE_ARCH = "qwen3-4b"
TRAIN_ARCH = "stablelm-1.6b"

# Cached vs cache-free logits, as ||cached - free|| / ||free|| over every
# compared logit.  Both paths compute in bf16 (8 significant bits) with fp32
# accumulation but round at different points: K/V pass through the cache,
# attention spans a masked max_seq window instead of the exact prefix, and
# the projections see [B, 1] rows instead of [B, S].  Every layer re-rounds
# the residual stream, so the gap grows with depth: 1.6e-2 at 36 layers of
# d_model 256-512 on the CPU, where an off-by-one cache index gives 0.17 to
# 0.20.  5e-2 sits between the two.
CACHE_REL_TOL = 5e-2
# Flash attention vs the fp32 oracle on bf16 inputs: the kernel feeds the
# softmax weights to the PV matmul in bf16 (2^-9 relative each) and both
# round the output to bf16 (one ulp apart at most: 2^-8 relative), so
# |err| <= ATOL + RTOL * |ref| with both at 1e-2.
ATTN_ATOL = ATTN_RTOL = 1e-2
# One chip vs the 2x2 mesh, one step from the same state and batch: the
# mesh splits contractions and reduces partial sums in another order.  On
# four virtual CPU devices (reduced widths) the two agree to 1.3e-5 in loss
# and 7e-6 relative in gradient norm.  On a 2x2 v5e at full width they
# agree to 4.6e-5 and 1.1e-2: the bf16 gradients are summed per data
# shard and then across shards, rounding at other points than one chip.
LOSS_ATOL = 5e-3
GNORM_RTOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found only "
                 f"{devs[0].platform} devices; no result")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, JAX found "
                 f"{len(devs)}; no result")
    return devs


def check(name: str, err: float, tol: float) -> None:
    log(f"  check {name:34s} worst {err:.3e}  tol {tol:.1e}")
    if not err <= tol:
        raise AssertionError(f"{name}: worst error {err:.3e} exceeds {tol}")


# -- one chip ----------------------------------------------------------------

def cache_consistency(cfg, params, prompts: np.ndarray,
                      n_decode: int) -> float:
    """Greedy-decode ``n_decode`` tokens after prefilling ``prompts``
    [B, P], then run ``M.forward`` without a cache over the prompt and the
    generated tokens; returns ||cached - cache-free|| / ||cache-free|| over
    the logits of positions P-1 .. P+n_decode-1."""
    b, p = prompts.shape
    prefill = jax.jit(lambda pr, c, t: M.prefill(cfg, pr, t, c))
    decode = jax.jit(lambda pr, c, t, i: M.decode_step(cfg, pr, t, i, c))

    @jax.jit
    def cache_free(pr, tokens):
        s = tokens.shape[1]
        h, _ = M.forward(cfg, pr, M.embed(cfg, pr, tokens),
                         jnp.broadcast_to(jnp.arange(s), (b, s)))
        return M.logits_of(cfg, pr, h)

    caches = M.init_cache(cfg, b, p + n_decode)
    logits, caches = prefill(params, caches, jnp.asarray(prompts))
    cached = [logits[:, -1]]
    tokens = [jnp.argmax(cached[-1], axis=-1).astype(jnp.int32)]
    for i in range(n_decode):
        logits, caches = decode(params, caches, tokens[-1], jnp.int32(p + i))
        cached.append(logits)
        tokens.append(jnp.argmax(logits, axis=-1).astype(jnp.int32))
    seq = jnp.concatenate([jnp.asarray(prompts),
                           jnp.stack(tokens[:-1], axis=1)], axis=1)
    want = cache_free(params, seq)[:, p - 1:].astype(jnp.float32)
    got = jnp.stack(cached, axis=1).astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def phase_serve(dev, seed: int) -> None:
    log(f"[serve] {SERVE_ARCH} published size: 16 requests, batch 8, "
        "prompt 1024, 128 new tokens")
    res = serve(SERVE_ARCH, n_requests=16, batch=8, prompt_len=1024,
                max_new=128, reduced=False, seed=seed)
    for k, v in res.items():
        log(f"  {k}: {v}")
    log(f"  peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    if res["requests"] != 16 or res["tokens"] != 16 * 128:
        raise AssertionError(f"served {res['requests']} requests, "
                             f"{res['tokens']} tokens; want 16, 2048")
    if not np.isfinite([res["tokens_per_s"], res["latency_ms_p99"]]).all():
        raise AssertionError("non-finite serving metrics")


def phase_cache(seed: int) -> None:
    cfg = configs.get(SERVE_ARCH)
    log(f"[cache] {SERVE_ARCH}: 2 prompts of 512, prefill + 8 decode steps "
        "vs cache-free forward")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (2, 512), dtype=np.int32)
    check("cache_vs_forward (rel logits)",
          cache_consistency(cfg, params, prompts, 8), CACHE_REL_TOL)


def _exact(name, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    check(name + " (mismatches)", float(np.sum(got != want)), 0)


def _attn(name, got, want) -> None:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    excess = np.abs(got - want) - ATTN_RTOL * np.abs(want)
    check(name + " (abs - rtol*|ref|)", float(excess.max()), ATTN_ATOL)


def phase_kernels(seed: int) -> None:
    log("[kernels] compiled Pallas kernels vs repro.kernels.ref")
    rng = np.random.default_rng(seed)

    def ints(shape, dtype=np.int32):
        info = np.iinfo(dtype)
        return jnp.asarray(rng.integers(info.min, info.max, shape, dtype))

    stack = ints((8, 512, 4096))
    for op in ("and", "or", "xor", "nand", "nor"):
        _exact(f"mws_{op}", ops.mws_bitwise(stack, op),
               ref.ref_mws(stack, op))
    for dtype in (np.int32, np.int8):
        a, b = ints((512, 4096), dtype), ints((512, 4096), dtype)
        tag = np.dtype(dtype).name
        _exact(f"bitserial_add_{tag}", ops.bitserial_add(a, b),
               ref.ref_bitserial_add(a, b))
        _exact(f"bitserial_mul_{tag}", ops.bitserial_mul(a, b),
               ref.ref_bitserial_mul(a, b))
    a, b = ints((512, 4096)), ints((512, 4096))
    _exact("shift_add_mul", ops.shift_add_mul(a, b, bits=8),
           ref.ref_shift_add_mul(a, b, 8))
    a8, b8 = ints((1024, 2048), np.int8), ints((2048, 1024), np.int8)
    _exact("int8_matmul", ops.int8_matmul(a8, b8),
           ref.ref_int8_matmul(a8, b8))
    pages = ints((512, 4096))
    query = pages[7, 64:68]                  # record 16 of page 7 matches
    hits = ops.search_pages(pages, query)
    _exact("search", hits, ref.ref_search(pages, query))
    if not bool(hits[7, 16]):
        raise AssertionError("search missed the planted record")

    def qkv(h, s):
        return [jnp.asarray(rng.standard_normal((h, s, 128)), jnp.bfloat16)
                for _ in range(3)]

    def oracle(q, k, v, causal):
        # fp32 on the TPU needs "highest"; the kernel keeps its own bf16
        # MXU passes, so only the oracle runs under it
        with jax.default_matmul_precision("highest"):
            return ref.ref_attention(q, k, v, causal=causal)

    q, k, v = qkv(8, 2048)
    for causal in (True, False):
        _attn(f"attention_2k_causal={causal}",
              ops.flash_attention(q, k, v, causal=causal),
              oracle(q, k, v, causal))
    # 32k: the oracle's [h, 32k, 32k] scores do not fit, so check the last
    # 512 query rows (the oracle aligns a short q to the end of k)
    q, k, v = qkv(8, 32768)
    got = ops.flash_attention(q, k, v, causal=True)[:, -512:]
    _attn("attention_32k_causal_last512", got,
          oracle(q[:, -512:], k, v, True))


# -- four chips --------------------------------------------------------------

def _state_bytes(state) -> int:
    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(state))


def phase_sharded_train(devs, seed: int) -> None:
    mesh = make_mesh((2, 2), ("data", "model"), devices=devs[:4])
    cut = dataclasses.replace(configs.get(TRAIN_ARCH), n_layers=2)
    log(f"[train] {TRAIN_ARCH} 2 layers at full width, batch 8 x 1024: "
        "one chip vs (data=2, model=2)")
    one = train(cut, steps=1, batch=8, seq=1024, reduced=False, seed=seed)
    del one["state"]
    sharded = train(cut, steps=1, batch=8, seq=1024, reduced=False,
                    seed=seed, mesh=mesh)
    del sharded["state"]
    log(f"  one chip: loss {one['losses'][0]} grad_norm "
        f"{one['grad_norms'][0]}")
    log(f"  2x2 mesh: loss {sharded['losses'][0]} grad_norm "
        f"{sharded['grad_norms'][0]}")
    check("loss one-chip vs mesh (abs)",
          abs(one["losses"][0] - sharded["losses"][0]), LOSS_ATOL)
    check("grad_norm one-chip vs mesh (rel)",
          abs(one["grad_norms"][0] - sharded["grad_norms"][0])
          / one["grad_norms"][0], GNORM_RTOL)

    log(f"[train] {TRAIN_ARCH} all 24 layers, 3 steps on the 2x2 mesh, "
        "batch 8 x 1024")
    res = train(TRAIN_ARCH, steps=3, batch=8, seq=1024, reduced=False,
                seed=seed, mesh=mesh)
    if not np.isfinite(res["losses"]).all():
        raise AssertionError(f"non-finite losses {res['losses']}")
    total = _state_bytes(res["state"])
    quarter = total / 4
    log(f"  state_bytes: {total} (a quarter: {quarter:.0f})")
    for d in devs[:4]:
        stats = d.memory_stats()
        log(f"  {d}: bytes_in_use {stats['bytes_in_use']} "
            f"peak_bytes_in_use {stats['peak_bytes_in_use']}")
        # replicated norms and the live batch add a little to each quarter
        check(f"{d.id} bytes_in_use / quarter state - 1",
              abs(stats["bytes_in_use"] / quarter - 1), 0.25)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devs = require_tpu(args.chips)
    dev = devs[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    log(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded_train(devs, args.seed)
    else:
        phase_serve(dev, args.seed)
        phase_cache(args.seed)
        phase_kernels(args.seed)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
