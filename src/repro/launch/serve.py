"""Batched serving driver: static-batch decode over a request queue.

Serves a model (reduced config by default, published widths with
``--full``): requests carry fixed-length prompts; the server takes up to
``batch`` of them, prefills once, then decodes the whole batch step by
step until every request has ``max_new`` tokens, and takes the next batch.
Both step programs are compiled before the timed window opens
(``compile_s``), so throughput and per-request latency percentiles (the
serving analogue of the paper's Fig. 8 tail-latency study) hold no
compilation.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --requests 16 --batch 4 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --full \
      --requests 16 --batch 8 --prompt-len 1024 --max-new 128
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.cache import use_compile_cache
from repro.launch.specs import cache_shapes
from repro.launch.steps import build_prefill_step, build_serve_step
from repro.models import model as M
from repro.sim.stats import percentile


class Request:
    def __init__(self, rid: int, prompt: np.ndarray, max_new: int):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.generated: List[int] = []
        self.t_arrive = time.perf_counter()
        self.t_done: Optional[float] = None


def serve(arch: str, n_requests: int, batch: int, prompt_len: int,
          max_new: int, reduced: bool = True, seed: int = 0) -> dict:
    cfg = configs.get(arch)
    if reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(seed)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    max_seq = prompt_len + max_new

    # compile both step shapes up front; caches are donated (updated in place)
    t_compile = time.perf_counter()
    caches_in = cache_shapes(cfg, batch, max_seq)
    prefill_fn = jax.jit(build_prefill_step(cfg), donate_argnums=(1,)).lower(
        params, caches_in,
        {"tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)}
    ).compile()
    serve_fn = jax.jit(build_serve_step(cfg), donate_argnums=(1,)).lower(
        params, caches_in, jax.ShapeDtypeStruct((batch,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    compile_s = time.perf_counter() - t_compile

    queue = [Request(i, rng.integers(0, cfg.vocab, size=prompt_len,
                                     dtype=np.int32), max_new)
             for i in range(n_requests)]
    done: List[Request] = []
    t0 = time.perf_counter()
    total_tokens = 0

    while queue:
        active = [queue.pop(0) for _ in range(min(batch, len(queue)))]
        prompts = np.zeros((batch, prompt_len), np.int32)  # idle slots pad
        prompts[:len(active)] = np.stack([r.prompt for r in active])
        caches = M.init_cache(cfg, batch, max_seq)
        logits, caches = prefill_fn(params, caches,
                                    {"tokens": jnp.asarray(prompts)})
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        for step in range(max_new):
            toks = np.asarray(nxt)       # waits for the step on the device
            for r, tok in zip(active, toks):
                if r.t_done is None:
                    r.generated.append(int(tok))
                    total_tokens += 1
                    if len(r.generated) >= r.max_new:
                        r.t_done = time.perf_counter()
            if all(r.t_done is not None for r in active):
                break
            logits, caches = serve_fn(params, caches, nxt,
                                      jnp.int32(prompt_len + step))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for r in active:
            if r.t_done is None:
                r.t_done = time.perf_counter()
            done.append(r)

    wall = time.perf_counter() - t0     # every step was waited for above
    lat = [(r.t_done - r.t_arrive) * 1e3 for r in done]
    leaves = jax.tree_util.tree_leaves(params)
    return {
        "requests": len(done),
        "tokens": total_tokens,
        "params": sum(int(x.size) for x in leaves),
        "param_bytes": sum(int(x.nbytes) for x in leaves),
        "compile_s": compile_s,
        "tokens_per_s": total_tokens / wall,
        "wall_s": wall,
        "latency_ms_p50": percentile(lat, 50),
        "latency_ms_p99": percentile(lat, 99),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=configs.ARCHS)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    use_compile_cache()
    res = serve(args.arch, args.requests, args.batch, args.prompt_len,
                args.max_new, reduced=not args.full)
    for k, v in res.items():
        print(f"  {k}: {v:.2f}" if isinstance(v, float) else f"  {k}: {v}")


if __name__ == "__main__":
    main()
