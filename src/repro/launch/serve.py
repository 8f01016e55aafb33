"""Serving driver: batched prefill + decode with a paged-in request queue.

``--arch <id> --smoke`` runs a reduced config end-to-end on CPU: a queue of
synthetic prompts is prefilled in batches, then decoded token-by-token with
a shared KV/state cache (continuous batch of equal-length requests —
slot-level batching; admission happens between decode bursts).

The full-size serving path is exercised (lower+compile only) by
``launch/dryrun.py`` on the production meshes — the decode/prefill step
functions here are the same ones the dry-run lowers.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.cache import enable_compile_cache
from repro.models import lm as lm_lib


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step")

    key = jax.random.PRNGKey(args.seed)
    params = lm_lib.init_lm(cfg, key)
    s_max = args.prompt_len + args.gen_len

    prefill = jax.jit(lambda p, t: lm_lib.prefill_step(cfg, p, t))
    decode = jax.jit(lambda p, st, t, pos: lm_lib.decode_step(
        cfg, p, st, t, pos))

    # Request queue: synthetic prompts, admitted in fixed-size batches.
    rng = np.random.default_rng(args.seed)
    queue = [rng.integers(0, cfg.vocab_size, (args.prompt_len,))
             for _ in range(args.requests)]

    done = 0
    t0 = time.perf_counter()
    tokens_out = 0
    while queue:
        batch_prompts = [queue.pop(0) for _ in range(
            min(args.batch, len(queue) + 1)) if queue or True][:args.batch]
        while len(batch_prompts) < args.batch:   # pad the last batch
            batch_prompts.append(batch_prompts[-1])
        toks = jnp.asarray(np.stack(batch_prompts), jnp.int32)

        # prefill gives the state at prompt_len; decode state buffers are
        # sized to s_max, so we re-seat the prefill caches into full-size
        # buffers (slot copy) before decoding.
        logits, pstate = prefill(params, toks)
        state = lm_lib.init_decode_state(cfg, args.batch, s_max)
        state = _seat(state, pstate)

        cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        for i in range(args.gen_len):
            pos = jnp.int32(args.prompt_len + i)
            logits, state = decode(params, state, cur, pos)
            cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            tokens_out += args.batch
        done += args.batch

    wall = time.perf_counter() - t0
    report = {"arch": args.arch, "requests": done,
              "tokens": tokens_out, "wall_s": round(wall, 2),
              "tok_per_s": round(tokens_out / wall, 1)}
    print(report)
    return report


def _seat(full_state, prefill_state):
    """Copy prefill caches into the (larger) decode buffers, leaf-wise.

    Works for flat ((B, S, ...)) and scan-stacked ((L, B, S, ...)) caches:
    the prefix copy happens along the first dim where shapes differ (the
    sequence dim).
    """
    import jax

    def seat(f, p):
        if p.shape == f.shape:
            return p.astype(f.dtype)
        dim = next(i for i, (a, b) in enumerate(zip(f.shape, p.shape))
                   if a != b)
        if p.shape[dim] > f.shape[dim]:
            # windowed prefill caches are padded to the full window; the
            # decode buffer may be smaller (s_max < window): truncate —
            # slots past s_max are empty by construction.
            sl = tuple([slice(None)] * dim + [slice(0, f.shape[dim])]
                       + [slice(None)] * (f.ndim - dim - 1))
            return p[sl].astype(f.dtype)
        sl = tuple([slice(None)] * dim + [slice(0, p.shape[dim])]
                   + [slice(None)] * (f.ndim - dim - 1))
        return f.at[sl].set(p.astype(f.dtype))

    return jax.tree_util.tree_map(seat, full_state, prefill_state)


if __name__ == "__main__":
    main()
