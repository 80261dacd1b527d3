"""The FLOP and byte functions reproduce the counts the benchmark states."""
import json
from pathlib import Path

import pytest

from chipbench import flops as F

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def shape(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["arch_config"]


def test_qwen3_4b_weights_and_kv():
    c = shape("qwen3-4b")
    assert F.param_count(c) == 4_022_468_096
    assert F.weight_bytes(c) == pytest.approx(8.04e9, rel=1e-3)
    assert F.kv_bytes_per_token(c) == 147_456
    # 16 sequences of max_seq 1280: the whole dense cache the program holds
    assert 16 * 1280 * F.kv_bytes_per_token(c) == pytest.approx(3.02e9,
                                                                rel=1e-3)


def test_decode_bound_is_bandwidth():
    c = shape("qwen3-4b")
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = [768] * 16
    bound = F.decode_step_bound_s(c, ctx, peaks)
    assert bound == pytest.approx(F.decode_step_bytes(c, ctx) / 819e9)
    assert F.decode_step_bytes(c, ctx) == pytest.approx(
        8.0449e9 + 16 * 768 * 147_456, rel=1e-4)

