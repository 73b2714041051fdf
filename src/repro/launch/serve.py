"""Decode-demo launcher: batched *autoregressive generation* with KV
cache / SSM state for the generative architectures (qwen/vlm/audio
families).  This is a throughput demo of ``backbones.decode_step``, not
an online service: it generates a fixed number of tokens from random
prompts and exits.

Not to be confused with ``repro.launch.serve_embed``, the *online
embedding serving* launcher — that one runs the ``repro.serve`` engine
(admission control, continuous micro-batching, circuit breaker, cache,
hot checkpoint reload) over the CLIP towers and answers requests until
told to stop.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
        --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.launch.compile_cache import init_compile_cache
from repro.models import backbones as BB


def generate(params, cfg, state, prompt, max_len, gen, *, greedy=True,
             rng=None):
    """prompt: (B, P) int32.  Returns (B, P+gen) tokens."""
    B, P = prompt.shape

    @jax.jit
    def step(state, tok, pos):
        return BB.decode_step(params, cfg, state, tok, pos)

    # prefill by scanning the prompt through decode_step
    logits = None
    for t in range(P):
        logits, state = step(state, prompt[:, t:t + 1], jnp.int32(t))
    toks = [prompt]
    cur = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)[:, None]
    t0 = time.time()
    for t in range(P, P + gen):
        toks.append(cur.astype(jnp.int32))
        logits, state = step(state, cur.astype(jnp.int32), jnp.int32(t))
        cur = jnp.argmax(logits[:, :cfg.vocab_size], axis=-1)[:, None]
    jax.block_until_ready(logits)
    dt = time.time() - t0
    return jnp.concatenate(toks, axis=1), gen * B / max(dt, 1e-9)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    init_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = jax.random.PRNGKey(args.seed)
    params = BB.init_params(rng, cfg)
    max_len = args.prompt_len + args.gen

    batch = {}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            rng, (args.batch, cfg.n_image_tokens, cfg.vision_dim)) * 0.1
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            rng, (args.batch, max_len // cfg.audio_subsample, cfg.d_model)
        ) * 0.1
    state = BB.prepare_decode_state(params, cfg, batch, args.batch, max_len)
    prompt = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    toks, tps = generate(params, cfg, state, prompt, max_len, args.gen)
    print(f"arch={cfg.name} batch={args.batch} generated {args.gen} tokens "
          f"per sequence at {tps:.1f} tok/s (batched)")
    print("sample token ids:", np.asarray(toks[0, :24]))
    return toks


if __name__ == "__main__":
    main()
