"""Closed-loop serving through the program's own step programs.

``clients`` clients each send their next request when the previous one
has been answered. The server keeps ``repro.launch.serve.serve``'s policy:
a static batch of every waiting request, a fresh cache per batch from
``M.init_cache``, one prefill (``build_prefill_step``), then one decode step
(``build_serve_step``) per token with a greedy argmax outside the step and a
host sync per token. Both steps are compiled as ``serve()`` compiles them:
caches donated, shapes from ``launch/specs.cache_shapes``.

Set-up: weights from the seed, both programs, one short warm-up batch that
runs every program and eager op the window uses. The window opens at a
batch start; a traced run traces all of it. Once it has closed, one
finished request of every slot of the batch, each from a batch drawn from
the seed, is checked against the float32 reference (``reference/served.py``).
Should the window close before its first batch is answered, that batch is
served to its end past the close, untimed.

Traffic keys: ``clients``, ``prompt_len``, ``new_tokens``,
``warmup_decode_steps``, ``gap_limit``.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import harness as H
from chipbench.reference import dense_gqa as R
from chipbench.reference import served
from repro.launch.specs import cache_shapes
from repro.launch.steps import build_prefill_step, build_serve_step
from repro.models import model as M

now = time.perf_counter
GAP = "served_token_logit_gap"


class Server:
    """The program's serving loop over one batch at a time."""

    def __init__(self, cfg, params, batch: int, prompt_len: int,
                 new_tokens: int):
        self.cfg, self.params = cfg, params
        self.batch, self.prompt_len, self.new = batch, prompt_len, new_tokens
        self.max_seq = prompt_len + new_tokens
        caches_in = cache_shapes(cfg, batch, self.max_seq)
        i32 = jnp.int32
        self.prefill_fn = jax.jit(
            build_prefill_step(cfg), donate_argnums=(1,)).lower(
                params, caches_in,
                {"tokens": jax.ShapeDtypeStruct((batch, prompt_len), i32)}
        ).compile()
        self.serve_fn = jax.jit(
            build_serve_step(cfg), donate_argnums=(1,)).lower(
                params, caches_in, jax.ShapeDtypeStruct((batch,), i32),
                jax.ShapeDtypeStruct((), i32)).compile()
        self.decode_indices = []    # index of each decode step, in order

    def run_batch(self, prompts: np.ndarray, close: float = np.inf,
                  steps: int = None, finish: bool = False):
        """Serve one batch. Returns (tokens [B, n], arrival times [m]):
        the m <= n tokens each request received no later than ``close``
        were timed; with ``finish`` the batch is served to its end past
        ``close``, untimed, else it stops there (n = m)."""
        steps = self.new if steps is None else steps
        p = self.prompt_len
        with TraceAnnotation("batch_prep"):
            caches = M.init_cache(self.cfg, self.batch, self.max_seq)
            toks = jnp.asarray(prompts)
        with TraceAnnotation("prefill"):
            logits, caches = self.prefill_fn(self.params, caches,
                                             {"tokens": toks})
        with TraceAnnotation("sample"):
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        out = np.zeros((self.batch, steps), np.int32)
        times = []
        served = 0
        for step in range(steps):
            with TraceAnnotation("sync"):
                got = np.asarray(nxt)        # waits for the step
            t = now()
            if t > close and not finish:
                break
            out[:, step] = got
            served += 1
            if t <= close:
                times.append(t)
            if step == steps - 1:
                break
            with TraceAnnotation("decode"):
                logits, caches = self.serve_fn(self.params, caches, nxt,
                                               jnp.int32(p + step))
            self.decode_indices.append(p + step)
            with TraceAnnotation("sample"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        del caches, logits, nxt
        return out[:, :served], np.asarray(times)


class Tracing:
    """The profiler over the whole window, marked by a ``window`` span."""

    def __init__(self, trace_dir):
        self.span = None
        if trace_dir:
            H.start_trace(trace_dir)
            self.span = TraceAnnotation("window")
            self.span.__enter__()

    def stop(self) -> None:
        if self.span is None:
            return
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.span = None


class Clients:
    """``n`` closed-loop clients; client i's k-th prompt is drawn from the
    seed, so the same seed sends the same requests."""

    def __init__(self, seed: int, n: int, prompt_len: int, vocab: int):
        self.rng = np.random.default_rng([seed, 0x5E])
        self.n, self.prompt_len, self.vocab = n, prompt_len, vocab

    def next_batch(self) -> np.ndarray:
        return self.rng.integers(0, self.vocab, (self.n, self.prompt_len),
                                 dtype=np.int32)


def itl_gaps(times: np.ndarray, clients: int) -> np.ndarray:
    """Gaps between consecutive tokens of each request of a batch (every
    request of a static batch receives its token at the same sync)."""
    return np.repeat(np.diff(times), clients)


def run(ctx: H.Context) -> H.Outcome:
    t = ctx.cell.traffic
    cfg = H.arch_config(ctx.cell.config)
    b, p, n = t["clients"], t["prompt_len"], t["new_tokens"]
    params = jax.block_until_ready(
        M.init_params(cfg, H.seed_key(ctx.seed)))
    ctx.log(f"weights at {now() - ctx.t_start:.3f} s")
    server = Server(cfg, params, b, p, n)
    ctx.log(f"programs at {now() - ctx.t_start:.3f} s")
    clients = Clients(ctx.seed, b, p, cfg.vocab)
    warm = np.random.default_rng([ctx.seed, 0x3A]).integers(
        0, cfg.vocab, (b, p), dtype=np.int32)
    server.run_batch(warm, steps=t["warmup_decode_steps"] + 1)
    server.decode_indices.clear()
    setup_s = now() - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s")

    trace_dir = f"{ctx.tmp}/trace" if ctx.trace else None
    t_open = now()
    close = t_open + ctx.seconds
    tracing = Tracing(trace_dir)

    batches, attempted = [], 0
    while now() < close:
        with TraceAnnotation("admit"):
            prompts = clients.next_batch()
        attempted += b
        # a window that would close before any request is answered serves
        # its first batch to the end, untimed: the check needs answers
        toks, times = server.run_batch(prompts, close=close,
                                       finish=not batches)
        batches.append((prompts, toks, times))
    t_stop = now()
    tracing.stop()
    if trace_dir:
        ctx.log(f"trace written in {now() - t_stop:.3f} s")

    tokens = b * sum(len(tm) for _, _, tm in batches)
    gaps = np.concatenate([itl_gaps(tm, b) for _, _, tm in batches])
    if not gaps.size:
        raise H.NoResult("no request received two tokens in the window")
    end_to_end = {"tokens_per_s": tokens / ctx.seconds,
                  "itl_ms_p95": H.percentile(list(gaps * 1e3), 95),
                  "setup_s": setup_s}
    finished = [(pr, tk) for pr, tk, _ in batches if tk.shape[1] == n]
    memory_peak = H.memory_peak_bytes(ctx.devices)
    ctx.log(f"window: {end_to_end}, memory_peak_bytes {memory_peak}")

    # -- the check, after the window, with the program's state freed -------
    # slot i's request from a batch drawn from the seed, for every slot
    which = np.random.default_rng([ctx.seed, 0xC4]).integers(
        len(finished), size=b)
    seqs = np.stack([np.concatenate([finished[j][0][i], finished[j][1][i]])
                     for i, j in enumerate(which)])
    decode_indices = list(server.decode_indices) if ctx.trace else []
    del params, server
    gc.collect()
    ctx.log(f"live bytes before the check: "
            f"{sum(x.nbytes for x in jax.live_arrays())}")
    t_check = now()
    w = R.init_weights(ctx.shape, H.seed_key(ctx.seed))
    gap = served.token_gaps(ctx.shape, w, seqs, p)
    ctx.log(f"check of {len(seqs)} requests, {gap.size} tokens: "
            f"{now() - t_check:.3f} s")
    checks = [H.Check(GAP, float(gap.max()), t["gap_limit"])]
    readings = {"gap_p99": float(np.quantile(gap, 0.99)),
                "tokens_checked": int(gap.size)}
    control_checks = None
    if ctx.control:
        control = served.token_gaps(ctx.shape, w, seqs, p, control=True)
        control_checks = [H.Check(GAP, float(control.max()),
                                  t["gap_limit"])]
        readings["control_gap_p99"] = float(np.quantile(control, 0.99))
    host = {"memory_peak_bytes": memory_peak, "batch": b, "tokens": tokens,
            "decode_indices": decode_indices, "check": readings}
    return H.Outcome(attempted=attempted, failed=0, end_to_end=end_to_end,
                     checks=checks, host=host, trace_dir=trace_dir,
                     control_checks=control_checks)
