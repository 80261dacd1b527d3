"""Batched serving of a small model: static-batch decode over a
synthetic request queue with latency percentiles.

    PYTHONPATH=src python examples/serve_batched.py --arch qwen3-4b
"""
import argparse

from repro.launch.serve import serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()
    res = serve(args.arch, args.requests, args.batch, prompt_len=16,
                max_new=args.max_new, reduced=True)
    print(f"[serve] {res['requests']} requests, {res['tokens']} tokens, "
          f"{res['tokens_per_s']:.1f} tok/s, "
          f"p50 {res['latency_ms_p50']:.0f}ms "
          f"p99 {res['latency_ms_p99']:.0f}ms")


if __name__ == "__main__":
    main()
