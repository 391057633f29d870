"""Training launcher: runs any arch on the local device set (or, on a pod,
the production mesh) with checkpoint/restart and the synthetic pipeline.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-medium-14b \
        --smoke --steps 50 --batch 4 --seq 128 --ckpt-dir /tmp/ck
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data.tokens import TokenPipeline, TokenPipelineConfig
from repro.distributed.fault_tolerance import run_supervised
from repro.launch.cache import enable_compile_cache
from repro.models import api


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-interval", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family in ("audio", "vlm", "ivector"):
        raise SystemExit("use family-specific examples for audio/vlm/ivector")
    step_fn = jax.jit(api.make_train_step(cfg), donate_argnums=0)
    pipe_cfg = TokenPipelineConfig(vocab_size=cfg.vocab_size,
                                   seq_len=args.seq,
                                   global_batch=args.batch)

    t0 = time.time()
    losses = []

    def train_step(state, batch):
        state, m = step_fn(state, batch)
        losses.append(float(m["loss"]))
        if len(losses) % args.log_every == 0:
            tok_s = args.batch * args.seq * len(losses) / (time.time() - t0)
            print(f"step {len(losses):5d} loss {losses[-1]:.4f} "
                  f"({tok_s:,.0f} tok/s)")
        return state, m

    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir,
                                 save_interval=args.ckpt_interval)
        rep = run_supervised(
            init_state_fn=lambda: api.init_state(
                # repro-check: disable=SRC002
                cfg, jax.random.PRNGKey(0), max_seq=args.seq),
            train_step_fn=train_step,
            data_factory=lambda: TokenPipeline(pipe_cfg),
            n_steps=args.steps, ckpt=ckpt)
        print(f"done at step {rep.final_step}; restarts={rep.n_restarts}")
    else:
        # repro-check: disable=SRC002
        state = api.init_state(cfg, jax.random.PRNGKey(0), max_seq=args.seq)
        pipe = TokenPipeline(pipe_cfg)
        for _ in range(args.steps):
            batch = jax.tree.map(jnp.asarray, pipe.next())
            state, _ = train_step(state, batch)
    print(f"first loss {losses[0]:.4f} -> last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
