"""Serving launcher: batched prefill + autoregressive decode with KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, ShapeConfig
from repro.launch.cache import enable_compile_cache
from repro.models import api


def pad_cache(cache, target_len: int):
    """Grow a prefill cache's sequence dim to the serving window."""
    def grow(a):
        if a.ndim >= 3 and a.shape[2] < target_len:
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, target_len - a.shape[2])
            return jnp.pad(a, pad)
        return a
    return jax.tree.map(grow, cache)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    assert cfg.family in ("dense", "moe", "ssm"), \
        "serve.py drives token-LM archs; see examples/ for others"
    window = args.prompt_len + args.gen
    # demo driver: fixed seeds make runs comparable across hosts
    # repro-check: disable=SRC002
    params = api.init_params(cfg, jax.random.PRNGKey(0), max_seq=window)
    prefill = jax.jit(api.make_prefill_step(cfg))
    decode = jax.jit(api.make_decode_step(cfg), donate_argnums=1)

    # repro-check: disable=SRC002
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    t0 = time.time()
    cache, logits = prefill(params, {"tokens": prompts})
    if cfg.family != "ssm":
        cache = pad_cache(cache, window)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        cache, logits = decode(params, cache,
                               {"token": tok, "pos": pos})
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok))
    jax.block_until_ready(logits)
    t_dec = time.time() - t0
    gen = np.stack(out, axis=1)
    print(f"prefill: {args.batch}x{args.prompt_len} in {t_prefill:.3f}s; "
          f"decode: {args.gen - 1} steps in {t_dec:.3f}s "
          f"({args.batch * (args.gen - 1) / max(t_dec, 1e-9):.1f} tok/s)")
    print("sample generation (first row):", gen[0][:12])


if __name__ == "__main__":
    main()
