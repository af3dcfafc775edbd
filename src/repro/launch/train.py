"""End-to-end trainer: mesh + sharded step + intermittence-safe progress.

The training loop is written exactly like a SONIC loop nest:

  * the *step cursor* and *data position* live in a durable Cursor file,
    committed atomically after every step (loop continuation);
  * full (params, opt) checkpoints go to A/B slots with an atomic manifest
    flip every ``ckpt_interval`` steps (loop-ordered buffering);
  * steps are idempotent: data is addressed by step index, so re-executing
    an interrupted step reproduces identical state (verified bit-exact by
    tests/test_train_resume.py).

Usage (CPU example scale):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Cursor, SlotStore
from ..configs import ARCHS, get_config
from ..data import token_batches
from ..models import get_model
from ..optim import adamw, cosine_schedule
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh
from .shardings import tree_shardings


class SimulatedFailure(Exception):
    """Raised by the failure injector (tests / chaos drills)."""


@dataclass
class TrainResult:
    steps_run: int
    final_step: int
    losses: list
    wall_s: float


def make_train_step(cfg, api, opt):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(cfg, p, batch))(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss
    return train_step


def make_grad_fn(cfg, api):
    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: api.loss_fn(cfg, p, batch))(params)
    return grad_fn


def train(cfg, *, steps: int, batch: int, seq: int, ckpt_dir: str,
          ckpt_interval: int = 20, lr: float = 3e-4, seed: int = 0,
          mesh=None, fail_at_step: int | None = None,
          log_every: int = 10) -> TrainResult:
    api = get_model(cfg)
    opt = adamw(lr=cosine_schedule(lr, warmup=max(steps // 20, 1),
                                   total=steps))
    mesh = mesh or make_host_mesh((jax.device_count(), 1))
    store = SlotStore(Path(ckpt_dir) / "state")
    cursor = Cursor(Path(ckpt_dir) / "cursor.json")

    # ---- restore or init (loop continuation: never restart from scratch)
    params_like = jax.eval_shape(lambda: api.init_params(cfg,
                                                         jax.random.key(seed)))
    p_shard = tree_shardings(params_like, mesh)
    state, meta = store.restore(like=None)
    if state is not None and meta and meta.get("step") is not None:
        # resume: restore the A/B front slot and replay deterministically
        # from its step (the step cursor ahead of it is observability only;
        # restartable progress is bounded by the durable state)
        start_step = int(meta["step"])
        params_flat, treedef = jax.tree.flatten(params_like)
        n_p = len(params_flat)
        params = jax.tree.unflatten(treedef, state[:n_p])
        opt_like = jax.eval_shape(opt.init, params_like)
        _, opt_treedef = jax.tree.flatten(opt_like)
        opt_state = jax.tree.unflatten(opt_treedef, state[n_p:])
    else:
        start_step = 0
        params = api.init_params(cfg, jax.random.key(seed))
        opt_state = opt.init(params)

    o_shard = tree_shardings(jax.eval_shape(opt.init, params_like), mesh,
                             zero1=True)
    step_fn = jax.jit(make_train_step(cfg, api, opt),
                      in_shardings=(p_shard, o_shard, None),
                      out_shardings=(p_shard, o_shard, None),
                      donate_argnums=(0, 1))

    losses = []
    t0 = time.time()
    steps_run = 0
    data = token_batches(cfg.vocab_size, batch, seq, steps, seed=seed)
    for step, batch_np in enumerate(data):
        if step < start_step:         # data stream is addressed by step
            continue
        if fail_at_step is not None and step == fail_at_step:
            raise SimulatedFailure(f"injected failure at step {step}")
        batch_j = {k: jax.numpy.asarray(v) for k, v in batch_np.items()}
        params, opt_state, loss = step_fn(params, opt_state, batch_j)
        losses.append(float(loss))
        steps_run += 1
        # loop-continuation commit: O(bytes of cursor), every step
        cursor.commit(step=step + 1, data_seed=seed)
        if (step + 1) % ckpt_interval == 0 or step + 1 == steps:
            leaves = jax.tree.leaves(params) + jax.tree.leaves(opt_state)
            store.save(leaves, meta={"step": step + 1, "cfg": cfg.name})
            cursor.commit(step=step + 1, checkpointed=step + 1)
        if log_every and (step + 1) % log_every == 0:
            print(f"step {step+1}/{steps} loss={float(loss):.4f}",
                  flush=True)
    return TrainResult(steps_run, start_step + steps_run, losses,
                       time.time() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-interval", type=int, default=20)
    args = ap.parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.scaled_down()
    res = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                ckpt_dir=args.ckpt_dir, ckpt_interval=args.ckpt_interval,
                lr=args.lr)
    print(f"ran {res.steps_run} steps to step {res.final_step}; "
          f"loss {res.losses[0]:.4f} -> {res.losses[-1]:.4f} "
          f"in {res.wall_s:.1f}s")


if __name__ == "__main__":
    main()


# --------------------------------------------------------------------------
# Microbatch-level loop continuation (the paper's in-loop cursor, for real)
# --------------------------------------------------------------------------

def train_microbatched(cfg, *, steps: int, batch: int, seq: int,
                       microbatches: int, ckpt_dir: str, lr: float = 3e-4,
                       seed: int = 0, fail_at: tuple | None = None,
                       log_every: int = 0) -> TrainResult:
    """Gradient-accumulation trainer whose progress cursor is the
    (step, microbatch) pair -- the exact fleet analogue of SONIC's loop
    continuation:

      * (params, opt) checkpoint to A/B slots at every step boundary
        (loop-ordered buffering: the committed front slot is never torn);
      * the f32 gradient accumulator + microbatch cursor commit durably
        after EVERY microbatch, so a mid-step failure re-executes at most
        one microbatch (vs the whole step -- or the whole interval -- for
        checkpoint-only recovery);
      * microbatches are idempotent: data is addressed by (step, mb), so
        re-execution is bit-exact (tests/test_train_resume.py).

    ``fail_at=(step, mb)`` injects a failure just before that microbatch.
    """
    assert batch % microbatches == 0
    mb_size = batch // microbatches
    api = get_model(cfg)
    opt = adamw(lr=lr)
    state_store = SlotStore(Path(ckpt_dir) / "state")
    accum_store = SlotStore(Path(ckpt_dir) / "accum")
    cursor = Cursor(Path(ckpt_dir) / "cursor.json")

    grad_fn = jax.jit(make_grad_fn(cfg, api))

    def apply_update(params, opt_state, mean_grads):
        return opt.update(mean_grads, opt_state, params)

    apply_jit = jax.jit(apply_update)

    # ---- restore --------------------------------------------------------
    params_like = jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.key(seed)))
    p_flat, p_def = jax.tree.flatten(params_like)
    state, meta = state_store.restore()
    if state is not None and meta:
        start_step = int(meta["step"])
        params = jax.tree.unflatten(p_def, state[:len(p_flat)])
        opt_like = jax.eval_shape(opt.init, params_like)
        _, o_def = jax.tree.flatten(opt_like)
        opt_state = jax.tree.unflatten(o_def, state[len(p_flat):])
    else:
        start_step = 0
        params = api.init_params(cfg, jax.random.key(seed))
        opt_state = opt.init(params)

    cur = cursor.read()
    start_mb = 0
    accum = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                         params_like)
    if (cur.get("step") == start_step and cur.get("mb", 0) > 0):
        saved, ameta = accum_store.restore()
        if saved is not None and ameta and \
                ameta.get("step") == start_step and \
                ameta.get("mb") == cur["mb"]:
            start_mb = int(cur["mb"])      # resume mid-step
            accum = jax.tree.unflatten(p_def, saved)

    losses = []
    t0 = time.time()
    steps_run = 0
    for step in range(start_step, steps):
        rs = np.random.default_rng(seed + 104729 * step)
        step_tokens = rs.choice(cfg.vocab_size, size=(batch, seq)
                                ).astype(np.int32)
        step_tokens[:, 1::2] = step_tokens[:, 0:-1:2]
        mb0 = start_mb if step == start_step else 0
        if mb0 == 0:
            accum = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params_like)
        for mb in range(mb0, microbatches):
            if fail_at is not None and (step, mb) == tuple(fail_at):
                raise SimulatedFailure(f"injected at step {step} mb {mb}")
            sl = slice(mb * mb_size, (mb + 1) * mb_size)
            bj = {"tokens": jax.numpy.asarray(step_tokens[sl]),
                  "labels": jax.numpy.asarray(step_tokens[sl])}
            loss, grads = grad_fn(params, bj)
            accum = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), accum, grads)
            # SONIC commit: durable accumulator (A/B slots) + cursor word
            accum_store.save(jax.tree.leaves(accum),
                             meta={"step": step, "mb": mb + 1})
            cursor.commit(step=step, mb=mb + 1)
            losses.append(float(loss))
        mean_grads = jax.tree.map(lambda a: a / microbatches, accum)
        params, opt_state = apply_jit(params, opt_state, mean_grads)
        steps_run += 1
        state_store.save(jax.tree.leaves(params) + jax.tree.leaves(opt_state),
                         meta={"step": step + 1})
        cursor.commit(step=step + 1, mb=0)
        if log_every and (step + 1) % log_every == 0:
            print(f"step {step+1}/{steps} loss={losses[-1]:.4f}", flush=True)
    return TrainResult(steps_run, steps, losses, time.time() - t0)
