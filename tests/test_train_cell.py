"""The training cell of the benchmark (``chipbench/drivers/train_steps.py``)
end to end through ``chipbench/run.py``'s harness on the CPU, at a size the
CPU runs in seconds: a tiny StableLM-shaped cell from new files and new
entries of a copy of the benchmark, on a 1x1 mesh. The published cell's
limits and readings are in PERF.md; the tiny cell's limits are its own."""
import json
import shutil
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import harness as H  # noqa: E402
from chipbench import run as RUN  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench import train_flops as TF  # noqa: E402
from repro.models import scopes as SC  # noqa: E402

CELL = "tiny-stablelm.tiny-train"
PUBLISHED = json.loads(
    (REPO / "chipbench/configs/stablelm-1.6b.json").read_text())
SEEDS = (2**31 + 31, 2**31 + 32)
FIXTURE = REPO / "chipbench" / "tests" / "data" / "small.xplane.pb"
TINY_SHAPE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
              "d_head": 16, "d_ff": 128, "vocab": 512, "layernorm": True,
              "rotary_fraction": 0.25, "qkv_bias": True, "qk_norm": False,
              "norm_eps": 1e-5, "rope_theta": 10000.0,
              "tie_embeddings": False, "remat": True, "dtype": "bfloat16"}
# limits of the tiny cell, from its readings on the CPU after 4 to 185
# steps: the program's loss gap up to 9e-4, grad norm gap up to 5.2e-3,
# worst leaf 0.016-0.052, whole update 0.052-0.072 and update size up to
# 0.09; the fp8 control's worst leaf 0.16-0.43 and whole update 0.18-0.21
# (its loss and grad norm gaps fall on both sides of the program's, its
# update size 0.14-0.49); an update dropped reads 1 in both update checks
TINY_TRAFFIC = {"driver": "train_steps", "mesh": [1, 1], "batch": 2,
                "seq": 64, "base_lr": 1e-3, "total_steps": 100,
                "lr_warmup": 10, "warmup_steps": 1, "reference_per": 1,
                "limits": {"train_loss_gap": 3e-3, "grad_norm_gap": 1.5e-2,
                           "grad_leaf_gap": 0.1, "param_update_gap": 0.12,
                           "update_size_gap": 0.5},
                "why": "two short sequences a step: a CPU test of the path"}


def make_checkout(tmp: Path) -> Path:
    """tmp/checkout: BENCHMARK.json and chipbench/ as committed, plus the
    tiny training cell's files and entries; the program is the repo's."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    base = root / "chipbench"
    conf = {"name": "tiny-stablelm", "arch": "stablelm-1.6b",
            "source": "test", "arch_config": TINY_SHAPE, "chips": 1,
            "optimizer": PUBLISHED["optimizer"]}
    (base / "configs/tiny-stablelm.json").write_text(json.dumps(conf))
    (base / "traffic/tiny-train.json").write_text(json.dumps(TINY_TRAFFIC))
    peaks = json.loads((base / "peaks.json").read_text())
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
    (base / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-stablelm", "source": "test",
                             "file": "chipbench/configs/tiny-stablelm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-stablelm",
                               "traffic": "tiny-train", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "stablelm-1.6b.train-2x2" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cpu_chips(n):
    return jax.devices()[:n]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("bench"))


def args(seed, trace=0):
    return ["--workload", CELL, "--seed", str(seed), "--seconds", "1",
            "--trace", str(trace)]


def test_the_training_cell_runs_from_its_files(checkout):
    res = json.loads(RUN.run(args(SEEDS[0]), root=checkout,
                             require_chips=cpu_chips))
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["checks"]) == set(TINY_TRAFFIC["limits"])
    assert res["device"]["count"] == 1


def test_the_program_passes_and_the_fp8_control_fails(checkout):
    cell = H.find_cell(checkout, CELL)
    drv = H.load_module(cell.base / "drivers/train_steps.py", "driver")
    ctx = H.Context(cell=cell, seed=SEEDS[1], seconds=0.5, trace=False,
                    devices=cpu_chips(1), peaks={}, t_start=0.0,
                    tmp=str(checkout), control=True)
    out = drv.run(ctx)
    assert all(c.ok for c in out.checks)
    assert not all(c.ok for c in out.control_checks)
    assert [c.name for c in out.control_checks] == [c.name
                                                    for c in out.checks]
    assert out.host["steps"] == out.attempted >= 1
    assert out.host["tokens_per_step"] == 2 * 64


@pytest.mark.parametrize("fault, failing", [
    ("params_unchanged", {"param_update_gap", "update_size_gap"}),
    ("one_leaf_unchanged", {"update_size_gap"}),
])
def test_a_step_that_drops_its_update_is_not_correct(checkout, monkeypatch,
                                                     fault, failing):
    """The timed step's AdamW update writes back the params it was given,
    all of them or one leaf (the value biases): its loss and grad norm,
    and the gradients of ``lm_loss``, are as sound as before, and only the
    update's checks fail."""
    from repro.launch import steps
    real = steps.adamw_update

    def dropped(params, grads, state, lr, **kw):
        new, state, metrics = real(params, grads, state, lr, **kw)
        if fault == "params_unchanged":
            return params, state, metrics
        new = jax.tree_util.tree_map_with_path(
            lambda path, n, p: p if "'bv'" in jax.tree_util.keystr(path)
            else n, new, params)
        return new, state, metrics
    monkeypatch.setattr(steps, "adamw_update", dropped)
    cell = H.find_cell(checkout, CELL)
    drv = H.load_module(cell.base / "drivers/train_steps.py", "driver")
    ctx = H.Context(cell=cell, seed=SEEDS[0], seconds=0.05, trace=False,
                    devices=cpu_chips(1), peaks={}, t_start=0.0,
                    tmp=str(checkout))
    out = drv.run(ctx)
    assert {c.name for c in out.checks if not c.ok} == failing
    assert out.host["check"]["update_size_gap"] == pytest.approx(1.0)


@pytest.mark.parametrize("step", [0, 150, 5000])
def test_the_reference_adamw_step_is_the_programs(step):
    """``reference/adamw.py`` against ``optim.adamw_update`` and the
    cosine schedule, on float32 params (no rounding of the result) with
    moments from earlier steps and a clipped gradient."""
    import numpy as np
    from chipbench.reference import adamw as A
    from repro.optim import adamw_init, adamw_update, make_schedule
    opt = PUBLISHED["optimizer"]
    keys = jax.random.split(jax.random.PRNGKey(step), 4)
    params = {"w": jax.random.normal(keys[0], (16, 8)),
              "b": jax.random.normal(keys[1], (8,))}
    grads = {"w": 3 * jax.random.normal(keys[2], (16, 8)),
             "b": jax.random.normal(keys[3], (8,))}
    state = adamw_init(params)
    _, state, _ = adamw_update(params, jax.tree_util.tree_map(
        lambda g: 0.5 * g[::-1], grads), state, 1e-3)
    state = state._replace(step=jax.numpy.asarray(step, state.step.dtype))
    lr = make_schedule("cosine", 1e-3, 10_000)(step)
    assert A.lr_at(step, 1e-3, 10_000, 200, opt["lr_final_frac"]) == \
        pytest.approx(float(lr), rel=1e-6)
    want, _, _ = adamw_update(params, grads, state, lr)
    got = A.new_params(params, grads, state.mu, state.nu, step, float(lr),
                       jax.numpy.float32, **{k: opt[k] for k in (
                           "b1", "b2", "eps", "weight_decay", "clip_norm")})
    for k in params:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-7)


def test_the_per_layer_metrics_of_a_traced_run(checkout, monkeypatch):
    # the CPU has no device planes to trace: read the recorded v5e trace,
    # which holds no training step
    monkeypatch.setattr(T, "reduce_dir",
                        lambda d, chips: T.reduce(T.load(str(FIXTURE))))
    res = json.loads(RUN.run(args(SEEDS[0], trace=1), root=checkout,
                             require_chips=cpu_chips))
    assert set(res["metrics"]) == {"idle_share.train"}
    assert 0 <= res["metrics"]["idle_share.train"]["value"] < 100


def _readings(program_s, calls, chips=4):
    shape = json.loads(
        (REPO / "chipbench/configs/stablelm-1.6b.json").read_text()
    )["arch_config"]
    summary = T.Summary(window_s=10.0, busy_s=9.5,
                        program_s={"jit_train_step": program_s},
                        program_calls={"jit_train_step": calls},
                        device_ops=[], idle_gaps=[])
    return T.Readings(trace=summary, host={"batch": 6, "seq": 4096},
                      shape=shape, peaks={"bf16_flops": 197e12},
                      chips=chips)


def test_train_step_ms_and_mfu_readers():
    base = REPO / "chipbench/metrics"
    step_ms = H.load_module(base / "train_step_ms.py", "metric")
    mfu = H.load_module(base / "train_mfu.py", "metric")
    r = _readings(program_s=10 * 0.6139, calls=10)
    assert step_ms.read(r) == pytest.approx(613.9)
    # 0.30688 s at the roofline over 0.6139 s a step
    assert mfu.read(r) == pytest.approx(49.99, abs=0.01)
    assert step_ms.read(_readings(0.0, 0)) is None
    assert mfu.read(_readings(0.0, 0)) is None


def test_train_flops_of_the_published_cell():
    shape = json.loads(
        (REPO / "chipbench/configs/stablelm-1.6b.json").read_text()
    )["arch_config"]
    from chipbench import flops as F
    assert F.matmul_params_per_token(shape) == 1_438_646_272
    flops = TF.train_step_flops(shape, 6, 4096)
    assert flops == pytest.approx(2.418e14, rel=1e-3)
    assert flops / (4 * 197e12) == pytest.approx(0.307, rel=1e-3)


def test_train_scopes_prints_the_run_and_its_split(checkout, monkeypatch,
                                                   capsys):
    """``chipbench/train_scopes.py`` prints the traced run's result line,
    then the check's readings with the fp8 control's and the step's split
    by bucket (here of the recorded trace's one program), and leaves the
    harness as it found it."""
    from chipbench import scopes as S
    from chipbench import train_scopes as TS
    monkeypatch.setattr(T, "reduce_dir",
                        lambda d, chips: T.reduce(T.load(str(FIXTURE))))
    monkeypatch.setattr(S, "load_trace", lambda d, chips: T.load(
        str(FIXTURE)))
    monkeypatch.setattr(TS, "PROGRAM", "jit_small_step")
    before = (H.load_module, H.Context, T.reduce_dir)
    assert TS.main(["--workload", CELL, "--seed", str(SEEDS[0]),
                    "--seconds", "1"], root=checkout,
                   require_chips=cpu_chips) == 0
    assert (H.load_module, H.Context, T.reduce_dir) == before
    line, extra = capsys.readouterr().out.strip().splitlines()[-2:]
    assert json.loads(line)["correct"] is True
    extra = json.loads(extra)
    assert extra["control_correct"] is False
    assert extra["check"]["control_grad_leaf_gap"] > 0.1
    split = extra["programs"]["jit_small_step"]
    assert list(split["buckets_ms"]) == list(SC.BUCKETS)
    assert sum(split["buckets_ms"].values()) == pytest.approx(
        split["self_ms"])
