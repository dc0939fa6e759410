"""Training driver: mesh + shardings + jit train_step + checkpoint/restart.

Runs anywhere (CPU smoke to multi-pod): the mesh, config and batch size are
arguments; everything else derives from PartitionSpec trees.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --steps 50 --batch 8 --seq 128 --reduced --ckpt-dir /tmp/ckpt

Fault-tolerance loop (DESIGN.md §5): every step is checkpoint-addressable;
on failure the driver restores the latest complete checkpoint (same or
smaller mesh — runtime/elastic.py) and replays from there.  The data
pipeline is step-addressable so the replay is exact (data/tokens.py).
"""
from __future__ import annotations

import argparse
import time
from functools import partial

import jax

from repro.configs import get_config
from repro.data import TokenStream
from repro.launch import steps as S
from repro.launch.mesh import make_local_mesh
from repro.models import lm
from repro.optim import AdamWConfig, compress_grads, init_opt, init_residual
from repro.runtime import (
    HealthMonitor,
    RestartPolicy,
    latest_step,
    make_shardings,
    restore_checkpoint,
    save_checkpoint,
)
from repro.runtime.elastic import sanitize_shardings

__all__ = ["TrainLoop", "main"]


class TrainLoop:
    """Owns mesh, state, step fn; exposes run(n_steps) with restart hooks."""

    def __init__(self, cfg, opt_cfg: AdamWConfig, mesh, *, seq_len: int,
                 global_batch: int, ckpt_dir: str | None = None,
                 ckpt_every: int = 50, seed: int = 0,
                 compress_pod_grads: bool = False):
        self.cfg, self.opt_cfg, self.mesh = cfg, opt_cfg, mesh
        self.ckpt_dir, self.ckpt_every = ckpt_dir, ckpt_every
        self.stream = TokenStream(cfg.vocab, seq_len, global_batch, seed)
        self.compress = compress_pod_grads
        self.monitor = HealthMonitor(n_hosts=jax.process_count())
        self.policy = RestartPolicy()

        pspecs, ospecs = S.state_specs(cfg, opt_cfg)
        # sanitize against the abstract state: small smoke configs / meshes
        # (batch 2 on a 4-way data axis, 4 heads on a 16-way model axis) would
        # otherwise fail pjit's exact-divisibility check (same as dryrun.py)
        params_aval, opt_aval = S.abstract_state(cfg, opt_cfg)
        batch_aval = jax.eval_shape(lambda: self.stream.batch(0))
        self.p_sh = sanitize_shardings(make_shardings(mesh, pspecs), params_aval)
        self.o_sh = sanitize_shardings(make_shardings(mesh, ospecs), opt_aval)
        self.b_sh = sanitize_shardings(
            make_shardings(
                mesh,
                {"tokens": S.batch_spec(None), "labels": S.batch_spec(None)},
            ),
            batch_aval,
        )

        base_step = S.make_train_step(cfg, opt_cfg)
        if compress_pod_grads:
            base_step = self._wrap_compressed(base_step)
        with jax.set_mesh(mesh):
            self.step_fn = jax.jit(
                base_step,
                in_shardings=(self.p_sh, self.o_sh, self.b_sh)
                # the error-feedback residual inherits whatever sharding
                # compress_grads left on it — let pjit infer it
                + ((None,) if compress_pod_grads else ()),
                # pin state outputs to the state shardings: otherwise GSPMD
                # may emit params with a different placement and the next
                # call's in_shardings reject them
                out_shardings=(self.p_sh, self.o_sh, None)
                + ((None,) if compress_pod_grads else ()),
                donate_argnums=(0, 1),
            )
        self.params = None
        self.opt = None
        self.residual = None
        self.step = 0

    def _wrap_compressed(self, base_step):
        cfg, opt_cfg = self.cfg, self.opt_cfg

        def step_with_ef(params, opt, batch, residual):
            from repro.launch.steps import _value_and_grad_trainable

            loss, grads = _value_and_grad_trainable(lm.loss_fn)(params, batch, cfg)
            grads, residual = compress_grads(grads, residual)
            from repro.optim import apply_updates

            params, opt, metrics = apply_updates(params, grads, opt, opt_cfg)
            metrics["loss"] = loss
            return params, opt, metrics, residual

        return step_with_ef

    # -- state ---------------------------------------------------------------

    def init_state(self, seed: int = 0):
        with jax.set_mesh(self.mesh):
            init = jax.jit(
                partial(lm.init_params, cfg=self.cfg),
                out_shardings=self.p_sh,
            )
            self.params = init(jax.random.PRNGKey(seed))
            self.opt = jax.jit(
                partial(init_opt, cfg=self.opt_cfg), out_shardings=self.o_sh
            )(self.params)
        if self.compress:
            self.residual = init_residual(self.params)
        self.step = 0

    def maybe_restore(self) -> bool:
        if not self.ckpt_dir or latest_step(self.ckpt_dir) is None:
            return False
        like = (self.params, self.opt) if self.params is not None else (
            lm.abstract_params(self.cfg),
            jax.eval_shape(
                lambda: init_opt(lm.abstract_params(self.cfg), self.opt_cfg)),
        )
        (self.params, self.opt), self.step = restore_checkpoint(
            self.ckpt_dir, like, shardings=(self.p_sh, self.o_sh)
        )
        if self.compress:
            self.residual = init_residual(self.params)
        return True

    def save(self):
        if self.ckpt_dir:
            save_checkpoint(self.ckpt_dir, self.step, (self.params, self.opt))

    # -- loop ----------------------------------------------------------------

    def run(self, n_steps: int, log_every: int = 10, fail_at=None):
        """Train n_steps; ``fail_at`` (step->exception) enables test injection."""
        losses = []
        while self.step < n_steps:
            t0 = time.monotonic()
            batch = jax.device_put(self.stream.batch(self.step), self.b_sh)
            try:
                if fail_at is not None and self.step in fail_at:
                    fail_at.remove(self.step)
                    raise RuntimeError(f"injected failure at step {self.step}")
                if self.compress:
                    self.params, self.opt, metrics, self.residual = self.step_fn(
                        self.params, self.opt, batch, self.residual
                    )
                else:
                    self.params, self.opt, metrics = self.step_fn(
                        self.params, self.opt, batch
                    )
            except RuntimeError as e:
                action = self.policy.on_failure(self.step)
                if not self.ckpt_dir:
                    raise
                print(f"[fault] step {self.step}: {e} -> {action}")
                self.maybe_restore()
                continue
            self.step += 1
            dt = time.monotonic() - t0
            self.monitor.beat(jax.process_index(), self.step, dt)
            loss = float(metrics["loss"])
            losses.append(loss)
            if log_every and self.step % log_every == 0:
                print(f"step {self.step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")
            if self.ckpt_dir and self.step % self.ckpt_every == 0:
                self.save()
        return losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action="store_true",
                    help="shrink the arch to CPU-smoke size")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--compress-pod-grads", action="store_true")
    args = ap.parse_args(argv)
    from repro.runtime.cache import enable_compilation_cache

    enable_compilation_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    opt_cfg = AdamWConfig(lr_peak=args.lr, warmup_steps=min(20, args.steps // 3),
                          total_steps=args.steps)
    mesh = make_local_mesh()
    loop = TrainLoop(cfg, opt_cfg, mesh, seq_len=args.seq,
                     global_batch=args.batch, ckpt_dir=args.ckpt_dir,
                     ckpt_every=args.ckpt_every,
                     compress_pod_grads=args.compress_pod_grads)
    loop.init_state()
    if args.resume:
        loop.maybe_restore()
    losses = loop.run(args.steps)
    print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    main()
