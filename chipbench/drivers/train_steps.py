"""Training steps on a mesh through the program's own train step.

The mesh is ``launch.mesh.make_mesh`` of the traffic's ``mesh`` shape
over ("data", "model") and the cell's chips. The state (bf16 params,
float32 AdamW moments) is ``launch.train.make_state``, made in place with
``state_shardings``; the step is ``launch.train.jit_train_step`` (params
and optimizer state donated), with the configuration's schedule over the
traffic's ``base_lr`` and ``total_steps``. Each step does what
``launch.train.train`` does: the host makes the step's batch
(``SyntheticLM`` from the seed) and places it (span ``batch_prep``), runs
the step, then reads the loss and the grad norm (span ``sync``).

Set-up: the state, the compiled step and ``warmup_steps`` steps. The window
opens at a step's start and no step starts after it closes; a traced run
traces all of it. ``tokens_per_s`` is the tokens of the completed steps
over the time from the window's opening to the end of the last of them.

The check, after the window: the state the window left (params and AdamW
moments) is kept on the host, one more batch is drawn and one more step
run on it, which gives the program's loss, grad norm and new params. The
program's gradients of the same ``lm_loss`` at the kept params, under the
same mesh, and the step's loss and grad norm are then compared with the
float32 reference (``reference/stablelm.py``) at the kept params on that
batch: the relative gap in loss, in global grad norm and the worst
relative L2 gap of any parameter leaf's gradient. The step's update is
compared with the reference's AdamW step (``reference/adamw.py``) from the
reference's gradients, the kept moments and the configuration's
``optimizer`` at the schedule's lr for that step, its new params stored
in the configuration's dtype: the worst relative L2 gap of any leaf's
change (``param_update_gap``; an update that leaves the params as they
were reads 1).

Traffic keys: ``mesh``, ``batch``, ``seq``, ``base_lr``, ``total_steps``,
``lr_warmup`` (the schedule's warm-up, as the program takes it for
``total_steps``), ``warmup_steps``, ``reference_per``, ``limits``
(``train_loss_gap``, ``grad_norm_gap``, ``grad_leaf_gap``,
``param_update_gap``).
"""
from __future__ import annotations

import functools
import gc
import math
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from chipbench import harness as H
from chipbench.drivers.serve_closed_loop import Tracing
from chipbench.reference import adamw as A
from chipbench.reference import stablelm as R
from repro.data import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.train import jit_train_step, make_state, on_mesh
from repro.models import model as M

now = time.perf_counter
F32 = jnp.float32
NORMS = ("ln1", "ln1_b", "ln2", "ln2_b")


def layout(params) -> dict:
    """A tree of the program's params (or of their gradients) in the
    reference's layout."""
    seg = params["segments"][0]
    layers = {k: seg[k] for k in NORMS}
    layers.update(seg["attn"])
    layers.update(seg["mlp"])
    w = {k: params[k] for k in ("emb", "unemb", "ln_f", "ln_f_b")}
    w["layers"] = layers
    return w


def reference_weights(params) -> dict:
    """The program's params as the reference takes them: its layout, in
    float32, each leaf placed as it was."""
    return jax.tree_util.tree_map(lambda a: a.astype(F32), layout(params))


def by_name(w) -> dict:
    """{name: leaf} of the reference's layout, layers as ``layers.<k>``."""
    out = {k: v for k, v in w.items() if k != "layers"}
    out.update({f"layers.{k}": v for k, v in w["layers"].items()})
    return out


def gaps(loss, grad_norm, grads: dict, ref_loss, ref_grads: dict) -> dict:
    """The loss, grad norm and gradient numbers of the check; ``grads``
    and ``ref_grads`` by name (``by_name``)."""
    sq = lambda a: jnp.sum(jnp.square(a.astype(F32)))
    ref_norm = math.sqrt(sum(float(sq(g)) for g in ref_grads.values()))
    leaf = {k: float(jnp.sqrt(sq(grads[k].astype(F32) - g) / sq(g)))
            for k, g in ref_grads.items()}
    worst = max(leaf, key=leaf.get)
    return {"train_loss_gap": abs(float(loss) - float(ref_loss))
            / abs(float(ref_loss)),
            "grad_norm_gap": abs(float(grad_norm) - ref_norm) / ref_norm,
            "grad_leaf_gap": leaf[worst], "worst_leaf": worst,
            "leaf_gaps": leaf}


@jax.jit
def _change_sums(old, new, ref_new):
    """Sums of squares of ``new - old`` less ``ref_new - old``, of ``ref_new
    - old`` and of ``new - old``."""
    d_ref = ref_new.astype(F32) - old
    d = new.astype(F32) - old
    return (jnp.sum(jnp.square(d - d_ref)), jnp.sum(jnp.square(d_ref)),
            jnp.sum(jnp.square(d)))


def update_gaps(old: dict, new: dict, ref_new: dict) -> dict:
    """The step's change ``new - old`` against the reference's ``ref_new -
    old``, leaves by name: the relative L2 gap of the whole change, and the
    worst relative gap of a leaf's change in size. A step that leaves the
    params as they were reads 1 in both; one that leaves a single leaf
    the reference moves reads 1 in size. A leaf the reference leaves as
    it was (every update under half a step of the stored dtype) reads 1
    in size if the program moved it."""
    num = den = 0.0
    size = {}
    for k, r in ref_new.items():
        gap, ref_sq, new_sq = map(float, _change_sums(old[k], new[k], r))
        num += gap
        den += ref_sq
        size[k] = abs(math.sqrt(new_sq / ref_sq) - 1) if ref_sq \
            else float(new_sq > 0)
    worst = max(size, key=size.get)
    return {"param_update_gap": math.sqrt(num / den),
            "update_size_gap": size[worst], "worst_size_leaf": worst,
            "update_size_gaps": size}


def reference_update(cfg: dict, t: dict, w: dict, grads: dict,
                     opt) -> dict:
    """The reference's new params by name: its AdamW step from ``grads``
    at the kept params ``w`` (by name, float32) and the kept AdamW state
    ``opt`` (on the host)."""
    step = int(opt.step)
    lr = A.lr_at(step, t["base_lr"], t["total_steps"], t["lr_warmup"],
                 cfg["optimizer"]["lr_final_frac"])
    hyper = {k: cfg["optimizer"][k]
             for k in ("b1", "b2", "eps", "weight_decay", "clip_norm")}
    return A.new_params(w, grads, by_name(layout(opt.mu)),
                        by_name(layout(opt.nu)), step, lr,
                        jnp.dtype(cfg["arch_config"]["dtype"]), **hyper)


class Trainer:
    """The program's train step on its mesh, and its batches."""

    def __init__(self, cfg, traffic: dict, devices, seed: int):
        t = traffic
        self.cfg = cfg
        self.mesh = make_mesh(tuple(t["mesh"]), ("data", "model"),
                              devices=devices)
        shape = (t["batch"], t["seq"])
        self.step_fn, self.shardings, self.batch_sharding = jit_train_step(
            cfg, t["total_steps"], t["base_lr"], self.mesh, shape)
        self.data = SyntheticLM(cfg.vocab, t["seq"], t["batch"], seed=seed)
        with on_mesh(self.mesh):
            self.state = make_state(cfg, seed, self.shardings)
        self.next = 0           # index of the next batch drawn

    def batch(self) -> dict:
        with TraceAnnotation("batch_prep"):
            b = self.data.batch(self.next)
            self.next += 1
            return {k: jax.device_put(v, self.batch_sharding)
                    for k, v in b.items()}

    def step(self, batch) -> tuple:
        """One step; (loss, grad norm), read on the host."""
        with on_mesh(self.mesh):
            params, opt, metrics = self.step_fn(self.state["params"],
                                                self.state["opt"], batch)
        self.state = {"params": params, "opt": opt}
        with TraceAnnotation("sync"):
            return float(metrics["loss"]), float(metrics["grad_norm"])

    def grads(self, params, batch):
        """The program's gradients of ``lm_loss`` at ``params``."""
        sh = self.shardings["params"]
        fn = jax.jit(jax.grad(lambda p, b: M.lm_loss(
            self.cfg, p, b["tokens"], b["labels"])),
            in_shardings=(sh, self.batch_sharding), out_shardings=sh)
        with on_mesh(self.mesh):
            return fn(params, batch)


def reference(shape: dict, w, batch: dict, per: int, mul=R.mm):
    """The reference's (loss, grads by name) at ``w`` on ``batch``, its
    gradients placed as ``w`` is."""
    sh = jax.tree_util.tree_map(lambda a: a.sharding, w)
    rep = NamedSharding(next(iter(jax.tree_util.tree_leaves(sh))).mesh, P())
    place = functools.partial(jax.jit, in_shardings=(sh, rep, rep),
                              out_shardings=(rep, sh))
    loss, g = R.loss_and_grads(shape, w, batch["tokens"], batch["labels"],
                               mul=mul, per=per, jit=place)
    return loss, by_name(g)


def run(ctx: H.Context) -> H.Outcome:
    t = ctx.cell.traffic
    cfg = H.arch_config(ctx.cell.config)
    tr = Trainer(cfg, t, ctx.devices, ctx.seed)
    ctx.log(f"state at {now() - ctx.t_start:.3f} s")
    for _ in range(t["warmup_steps"]):
        tr.step(tr.batch())
    setup_s = now() - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s")

    tokens_per_step = t["batch"] * t["seq"]
    trace_dir = f"{ctx.tmp}/trace" if ctx.trace else None
    t_open = now()
    close = t_open + ctx.seconds
    tracing = Tracing(trace_dir)
    losses, failed, t_end = [], 0, t_open
    while now() < close:
        loss, gnorm = tr.step(tr.batch())
        t_end = now()
        losses.append(loss)
        failed += not (math.isfinite(loss) and math.isfinite(gnorm))
    tracing.stop()
    steps = len(losses)
    end_to_end = {"tokens_per_s": steps * tokens_per_step / (t_end - t_open),
                  "setup_s": setup_s}
    memory_peak = H.memory_peak_bytes(ctx.devices)
    ctx.log(f"window: {steps} steps, {end_to_end}, losses "
            f"{losses[0]:.4f} .. {losses[-1]:.4f}, "
            f"memory_peak_bytes {memory_peak}")

    # -- the check, on the timed path's state ---------------------------------
    t_check = now()
    # kept on the host: a copy on the chips would not fit beside the step
    kept = jax.device_get(tr.state)
    batch = tr.batch()
    loss, gnorm = tr.step(batch)
    new = by_name(layout(tr.state["params"]))
    del tr.state
    gc.collect()
    params = jax.device_put(kept["params"], tr.shardings["params"])
    grads = by_name(layout(tr.grads(params, batch)))
    w = reference_weights(params)
    del params
    host_batch = {k: jax.device_get(v) for k, v in batch.items()}
    per = t["reference_per"]
    ref_loss, ref_grads = reference(ctx.shape, w, host_batch, per)
    got = gaps(loss, gnorm, grads, ref_loss, ref_grads)
    del grads
    w_named = by_name(w)
    cfg_file, opt = ctx.cell.config, kept["opt"]
    ref_new = reference_update(cfg_file, t, w_named, ref_grads, opt)
    got.update(update_gaps(w_named, new, ref_new))
    ctx.log(f"check {got} in {now() - t_check:.3f} s")
    limits = t["limits"]
    checks = [H.Check(k, got[k], limits[k]) for k in limits]
    readings = dict(got, loss=loss, ref_loss=float(ref_loss))
    control_checks = None
    if ctx.control:
        c_loss, c_grads = reference(ctx.shape, w, host_batch, per, R.fp8_mm)
        c_norm = math.sqrt(sum(float(jnp.sum(jnp.square(g)))
                               for g in c_grads.values()))
        ctl = gaps(c_loss, c_norm, c_grads, ref_loss, ref_grads)
        ctl.update(update_gaps(w_named, reference_update(
            cfg_file, t, w_named, c_grads, opt), ref_new))
        control_checks = [H.Check(k, ctl[k], limits[k]) for k in limits]
        readings.update({f"control_{k}": v for k, v in ctl.items()})
    host = {"memory_peak_bytes": memory_peak, "steps": steps,
            "tokens_per_step": tokens_per_step, "batch": t["batch"],
            "seq": t["seq"], "losses": losses, "check": readings}
    return H.Outcome(attempted=steps, failed=failed, end_to_end=end_to_end,
                     checks=checks, host=host, trace_dir=trace_dir,
                     control_checks=control_checks)
