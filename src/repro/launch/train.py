"""Training driver.

CPU-scale end-to-end training of any ``--arch`` (reduced config by default)
with checkpoint/restart, deterministic data, straggler monitoring, and
optional fault injection.  Given a ``mesh``, ``train()`` places params,
optimizer state and batches with the ``launch/sharding.py`` rules and runs
every step under that mesh.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --steps 100 --batch 8 --seq 64 --ckpt-dir /tmp/ck
  # crash at step 37 and restart from the last checkpoint:
  ... --fail-at 37 --max-restarts 1
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLM
from repro.launch import sharding as SH
from repro.launch.cache import use_compile_cache
from repro.launch.elastic import (SimulatedFailure, StragglerMonitor,
                                  run_elastic)
from repro.launch.specs import opt_shapes, params_shapes
from repro.launch.steps import build_train_step
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.optim.adamw import adamw_init


def state_shardings(cfg: ArchConfig, mesh) -> dict:
    """NamedShardings of the training state ``{"params", "opt"}``."""
    p_shapes = params_shapes(cfg)
    named = lambda specs: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs)
    return {"params": named(SH.param_specs(cfg, p_shapes, mesh)),
            "opt": named(SH.opt_specs(opt_shapes(cfg, p_shapes), mesh))}


def make_state(cfg, seed: int, shardings: Optional[dict] = None):
    """Params and AdamW state from ``seed``; with ``shardings`` each leaf
    is created in place on its devices."""
    key = jax.random.PRNGKey(seed)
    if shardings is None:
        params = M.init_params(cfg, key)
        return {"params": params, "opt": adamw_init(params)}
    params = jax.jit(M.init_params, static_argnums=0,
                     out_shardings=shardings["params"])(cfg, key)
    opt = jax.jit(adamw_init, out_shardings=shardings["opt"])(params)
    return {"params": params, "opt": opt}


@contextlib.contextmanager
def on_mesh(mesh):
    """Bind the model's sharding hints to ``mesh`` for the duration:
    ``jax.set_mesh(mesh)``, or nothing for None."""
    with contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh):
        yield


def jit_train_step(cfg: ArchConfig, total_steps: int, base_lr: float,
                   mesh=None, batch_shape=None):
    """The jitted train step (params and optimizer state donated).  With
    a ``mesh`` it is pinned to the state's and the ``batch_shape`` token
    batch's shardings, which are returned beside it (else None)."""
    train_step = build_train_step(cfg, total_steps=total_steps,
                                  base_lr=base_lr)
    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0, 1)), None, None
    shardings = state_shardings(cfg, mesh)
    batch_sharding = NamedSharding(mesh, SH.batch_spec(batch_shape, mesh))
    step_fn = jax.jit(
        train_step, donate_argnums=(0, 1),
        in_shardings=(shardings["params"], shardings["opt"], batch_sharding),
        out_shardings=(shardings["params"], shardings["opt"],
                       NamedSharding(mesh, P())))
    return step_fn, shardings, batch_sharding


def train(arch, steps: int, batch: int, seq: int,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 20,
          reduced: bool = True, fail_at: Optional[int] = None,
          seed: int = 0, log_every: int = 10,
          resume: bool = True, base_lr: float = 1e-3, mesh=None) -> dict:
    """Train ``arch`` (a name in ``configs.ARCHS`` or an ArchConfig); on
    ``mesh`` when one is given, else on the default device."""
    cfg = configs.get(arch) if isinstance(arch, str) else arch
    if reduced:
        cfg = cfg.reduced()
    data = SyntheticLM(cfg.vocab, seq, batch, seed=seed)
    step_fn, shardings, batch_sharding = jit_train_step(
        cfg, steps, base_lr, mesh, (batch, seq))
    state = make_state(cfg, seed, shardings)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if mgr is not None and resume and mgr.latest_step() is not None:
        state, manifest = mgr.restore(state)
        start = manifest["step"]
        print(f"[train] resumed from checkpoint step {start}")

    mon = StragglerMonitor()
    losses, grad_norms = [], []
    with on_mesh(mesh):
        for step in range(start, steps):
            if fail_at is not None and step == fail_at:
                raise SimulatedFailure(
                    f"injected node failure at step {step}")
            t0 = time.time()
            np_batch = data.batch(step)
            jbatch = {k: jax.device_put(v, batch_sharding)
                      for k, v in np_batch.items()}
            params, opt, metrics = step_fn(state["params"], state["opt"],
                                           jbatch)
            state = {"params": params, "opt": opt}
            loss = float(metrics["loss"])       # waits for the step
            dt = time.time() - t0
            straggler = mon.observe(dt)
            losses.append(loss)
            grad_norms.append(float(metrics["grad_norm"]))
            if step % log_every == 0 or step == steps - 1:
                print(f"[train] step {step:5d} loss {loss:8.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {grad_norms[-1]:7.3f} "
                      f"{dt*1e3:7.1f}ms{'  STRAGGLER' if straggler else ''}")
            if mgr is not None and (step + 1) % ckpt_every == 0:
                mgr.save(step + 1, state,
                         extra={"arch": cfg.name, "loss": loss})
    if mgr is not None:
        mgr.save(steps, state, extra={"arch": cfg.name}, blocking=True)
        mgr.wait()
    return {"final_loss": losses[-1] if losses else None,
            "first_loss": losses[0] if losses else None,
            "stragglers": mon.flagged, "state": state,
            "losses": losses, "grad_norms": grad_norms}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=configs.ARCHS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--full", action="store_true",
                    help="use the full (non-reduced) config")
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()
    use_compile_cache()

    attempted = {"n": 0}

    def once(_resume_step):
        # fail only on the first attempt so the restart proves recovery
        fail = args.fail_at if attempted["n"] == 0 else None
        attempted["n"] += 1
        res = train(args.arch, args.steps, args.batch, args.seq,
                    ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                    reduced=not args.full, fail_at=fail, base_lr=args.lr)
        print(f"[train] done: first_loss={res['first_loss']:.4f} "
              f"final_loss={res['final_loss']:.4f} "
              f"stragglers={res['stragglers']}")
        return args.steps

    run_elastic(once, max_restarts=args.max_restarts,
                on_restart=lambda n, e: print(f"[elastic] restart #{n}: {e}"))


if __name__ == "__main__":
    main()
