"""The serving check separates sound runs from broken ones, at a size the
CPU holds: each fault is planted underneath the timed path of a whole run
(the harness's look for a chip skipped) and ``correct`` comes out false;
on every seed the fp8 control's result line reads ``correct`` false, the
program's true.

The tiny cell's limit (``_tiny.TINY_TRAFFIC``) is its own: the published
cell's limit and its readings are in PERF.md."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness as H
from chipbench import run as RUN
from chipbench.tests import _tiny

SEEDS = (2**31 + 21, 2**31 + 22, 2**31 + 23)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return _tiny.make_checkout(tmp_path_factory.mktemp("bench"))


def run_once(checkout, seed, patch=None):
    cell = H.find_cell(checkout, "tiny-gqa.tiny")
    drv = H.load_module(cell.base / "drivers/serve_closed_loop.py", "drv")
    if patch:
        patch(drv)
    ctx = H.Context(cell=cell, seed=seed, seconds=1.0, trace=False,
                    devices=jax.devices()[:1], peaks={}, t_start=0.0,
                    tmp=str(checkout), control=True)
    return drv.run(ctx)


def broken_server(wrap):
    """Patch the driver so that its Server's decode step is ``wrap``ped."""
    def patch(drv):
        init = drv.Server.__init__

        def __init__(self, *a, **k):
            init(self, *a, **k)
            self.serve_fn = wrap(self.serve_fn)
        drv.Server.__init__ = __init__
    return patch


def state_unchanged(fn):
    def step(params, caches, tok, idx):
        kept = jax.tree_util.tree_map(jnp.copy, caches)
        logits, _ = fn(params, caches, tok, idx)
        return logits, kept
    return step


def _altered(rows):
    """Every third step, another token wins in ``rows`` of the batch, where
    it is produced."""
    def wrap(fn):
        calls = {"n": 0}

        def step(params, caches, tok, idx):
            logits, caches = fn(params, caches, tok, idx)
            calls["n"] += 1
            if calls["n"] % 3 == 0:
                r = jnp.arange(logits.shape[0])[rows]
                worst = jnp.argmin(logits[r], axis=-1)
                logits = logits.at[r, worst].set(1e4)
            return logits, caches
        return step
    return wrap


token_altered = _altered(slice(None))
last_slot_altered = _altered(slice(-1, None))


def half_batch_left_out(fn):
    """The second half of the batch gets the first half's answers."""
    def step(params, caches, tok, idx):
        logits, caches = fn(params, caches, tok, idx)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), caches
    return step


def correct(checkout, out, checks):
    cell = H.find_cell(checkout, "tiny-gqa.tiny")
    line = H.result_line(cell, dataclasses.replace(out, checks=checks),
                         {}, {}, None)
    return json.loads(line)["correct"]


@pytest.mark.parametrize("seed", SEEDS)
def test_program_correct_and_control_not(checkout, seed):
    out = run_once(checkout, seed)
    assert correct(checkout, out, out.checks) is True
    assert correct(checkout, out, out.control_checks) is False
    assert [c.name for c in out.control_checks] == [
        c.name for c in out.checks]


@pytest.mark.parametrize("fault", [state_unchanged, token_altered,
                                   last_slot_altered, half_batch_left_out])
def test_fault_makes_the_run_incorrect(checkout, fault):
    out = run_once(checkout, SEEDS[0], broken_server(fault))
    assert correct(checkout, out, out.checks) is False


def test_fault_reaches_the_result_line(checkout, monkeypatch):
    cell_drv = {}

    def load(path, tag, _orig=H.load_module):
        mod = _orig(path, tag)
        if tag == "driver":
            broken_server(token_altered)(mod)
            cell_drv["m"] = mod
        return mod
    monkeypatch.setattr(H, "load_module", load)
    res = json.loads(RUN.run(
        ["--workload", "tiny-gqa.tiny", "--seed", str(SEEDS[1]),
         "--seconds", "1", "--trace", "0"],
        root=checkout, require_chips=_tiny.cpu_chips))
    assert res["correct"] is False
    gap = res["checks"]["served_token_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert np.isfinite(gap["value"])
