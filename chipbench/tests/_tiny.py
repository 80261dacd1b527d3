"""A checkout of the benchmark with one more cell, at a size the CPU runs
in seconds: its configuration, traffic mix and per-layer metric come from
new files and new entries alone."""
import json
import shutil
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[2]

TINY_SHAPE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "d_head": 16, "d_ff": 128, "vocab": 512, "qk_norm": True,
              "norm_eps": 1e-6, "rope_theta": 1000000.0,
              "tie_embeddings": True, "dtype": "bfloat16"}
TINY_TRAFFIC = {"driver": "serve_closed_loop", "clients": 4,
                "prompt_len": 16, "new_tokens": 24, "warmup_decode_steps": 1,
                "gap_limit": 0.015,
                "why": "four clients, short prompts: a CPU test of the path"}
READER = '''"""Tokens the run served (a test metric)."""


def read(r):
    return float(r.host["tokens"])
'''


def make_checkout(tmp: Path) -> Path:
    """tmp/<checkout>: BENCHMARK.json and chipbench/ as committed, plus the
    tiny cell's files and entries; the program is found in the repo."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = {"name": "tiny-gqa", "arch": "qwen3-4b",
            "source": "test", "arch_config": TINY_SHAPE, "chips": 1}
    (root / "chipbench/configs/tiny-gqa.json").write_text(json.dumps(conf))
    (root / "chipbench/traffic/tiny.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (root / "chipbench/metrics/tokens_served.py").write_text(READER)
    peaks = json.loads((root / "chipbench/peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
    (root / "chipbench/peaks.json").write_text(json.dumps(peaks))
    bench["configs"].append({"name": "tiny-gqa", "source": "test",
                             "file": "chipbench/configs/tiny-gqa.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-gqa.tiny", "config": "tiny-gqa",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny-gqa.tiny")
    bench["per_layer"].append({
        "name": "tokens_served", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "service",
        "moves": "tokens_per_s", "workloads": ["tiny-gqa.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cpu_chips(n):
    return jax.devices()[:n]
