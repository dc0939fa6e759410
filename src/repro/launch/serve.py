"""Serving driver: batched prefill + decode with a static batch scheduler.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
        --batch 4 --prompt-len 32 --gen 16

The scheduler is deliberately simple (static batch, greedy sampling) — the
serving *system* contribution lives in the sharding story: prefill and decode
are separately jitted with KV caches sequence-sharded over the model axis
(launch/steps.py cache_specs), which is what makes decode_32k / long_500k
lower on the production mesh.
"""
from __future__ import annotations

import argparse
import time

import jax

import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch import steps as S
from repro.launch.mesh import make_local_mesh
from repro.models import lm
from repro.runtime import make_shardings

__all__ = ["Server", "main"]


class Server:
    def __init__(self, cfg, mesh, max_len: int):
        self.cfg, self.mesh, self.max_len = cfg, mesh, max_len
        pspecs = lm.param_specs(cfg)
        self.p_sh = make_shardings(mesh, pspecs)
        with jax.set_mesh(mesh):
            self.params = jax.jit(
                lambda k: lm.init_params(k, cfg), out_shardings=self.p_sh
            )(jax.random.PRNGKey(0))
            self._prefill = jax.jit(
                lambda p, toks: lm.prefill(p, toks, cfg, max_len)
            )
            self._decode = jax.jit(
                lambda p, tok, c: lm.decode_step(p, tok, c, cfg)
            )

    def generate(self, prompts: np.ndarray, n_tokens: int):
        """prompts: (B, S) int32. Greedy decode n_tokens. Returns (B, n)."""
        with jax.set_mesh(self.mesh):
            logits, caches = self._prefill(self.params, jnp.asarray(prompts))
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            out = [tok]
            for _ in range(n_tokens - 1):
                logits, caches = self._decode(self.params, tok, caches)
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                out.append(tok)
        return np.concatenate([np.asarray(t) for t in out], axis=1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args(argv)
    from repro.runtime.cache import enable_compilation_cache

    enable_compilation_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_local_mesh()
    server = Server(cfg, mesh, max_len=args.prompt_len + args.gen)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.monotonic()
    out = server.generate(prompts, args.gen)
    dt = time.monotonic() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(out[:2, :12])
    return out


if __name__ == "__main__":
    main()
