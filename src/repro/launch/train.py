"""Distributed LM training driver with GRAD-MATCH subset selection.

``--arch <id>`` selects any assigned architecture (smoke-reduced with
``--smoke`` for CPU runs; the full configs are exercised via dryrun.py).
The loop is the production arrangement scaled down:

  - mesh from ``--mesh-data/--mesh-model`` over local devices,
  - params/optimizer sharded by ``distributed.sharding`` (FSDP optional),
  - stateless-indexed token pipeline (restartable by construction),
  - GRAD-MATCHPB candidate selection every R *steps* over a candidate
    window of W upcoming batches: proxies from ``lm.selection_proxy``
    (closed-form head gradient, no trunk backprop), sharded OMP from
    ``core.distributed``, selected micro-batches trained with weights,
  - async checkpointing (+ auto-resume), elastic re-shard on device-count
    change via ``launch/elastic.py``.

Example::

  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
      --steps 100 --select-every 20 --budget 0.25
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.core import distributed as dist_lib
from repro.core import gradmatch as gm_lib
from repro.data.tokens import TokenStream
from repro.distributed import hints
from repro.distributed.sharding import logical_rules, param_shardings
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import lm as lm_lib
from repro.optim import OptState, cosine_with_warmup, sgd
from repro.train.steps import lm_train_step_fn, make_lm_proxy_step


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8,
                    help="candidate micro-batches per selection window")
    ap.add_argument("--micro-batch", type=int, default=4,
                    help="sequences per micro-batch")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--budget", type=float, default=0.25,
                    help="fraction of candidate micro-batches to train on")
    ap.add_argument("--select-every", type=int, default=20, help="R (steps)")
    ap.add_argument("--window", type=int, default=16,
                    help="candidate window: micro-batches per selection")
    ap.add_argument("--strategy", default="gradmatch-pb",
                    choices=["gradmatch-pb", "random", "full"])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--checkpoint-dir")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lam", type=float, default=0.5)
    return ap


def init_params(cfg, key: jax.Array, mesh, fsdp: bool):
    """(params, shardings).  On a multi-device mesh one jit creates the
    parameters in their shardings, so under FSDP no device ever holds the
    whole tree.  On one device they are made eagerly, which skips that
    program's compile (114 s for xlstm-1.3b, compiled for a TPU v5e).
    The two agree bit for bit on the CPU but not on a TPU."""
    init = functools.partial(lm_lib.init_lm, cfg)
    if mesh.size == 1:
        params = init(key)
        p_sh = param_shardings(cfg, params, mesh, fsdp=fsdp)
        return jax.device_put(params, p_sh), p_sh
    p_sh = param_shardings(cfg, jax.eval_shape(init, key), mesh, fsdp=fsdp)
    return jax.jit(init, out_shardings=p_sh)(key), p_sh


def window_proxies(proxy_fn, params, stream: TokenStream, window_round: int,
                   window: int) -> jax.Array:
    """(window, d_model) candidate proxies: one mean head-gradient per
    upcoming micro-batch of the selection window."""
    return jnp.stack([
        jnp.mean(proxy_fn(params, stream.batch(window_round, s)), axis=0)
        for s in range(window)])


def select_window(mesh, proxies: jax.Array, k_batches: int,
                  lam: float) -> gm_lib.SelectionResult:
    """GRAD-MATCHPB over the window: the sharded OMP whenever the mesh has
    more than one data shard, the single-device solver otherwise."""
    if mesh.shape["data"] == 1:
        return gm_lib.gradmatch(proxies, k_batches, lam=lam)
    return dist_lib.sharded_omp_select(
        mesh, proxies, jnp.sum(proxies, axis=0), k_batches, axis="data",
        lam=lam)


def main(argv=None) -> dict:
    args = build_argparser().parse_args(argv)
    if args.strategy == "gradmatch-pb" and args.window % args.mesh_data:
        raise ValueError(
            f"--window {args.window} must be a multiple of --mesh-data "
            f"{args.mesh_data}: the sharded OMP splits the candidate window "
            "evenly over the data axis")
    enable_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    mesh = make_host_mesh(args.mesh_data, args.mesh_model)

    params, p_sh = init_params(cfg, jax.random.PRNGKey(args.seed), mesh,
                               args.fsdp)

    opt = sgd(cosine_with_warmup(args.lr, 10, args.steps), momentum=0.9)
    opt_state = opt.init(params)

    step_fn = jax.jit(lm_train_step_fn(cfg, opt), donate_argnums=(0, 1))
    proxy_fn = make_lm_proxy_step(cfg)

    stream = TokenStream(seed=args.seed, batch_per_shard=args.micro_batch,
                         seq_len=args.seq_len, vocab=cfg.vocab_size,
                         n_shards=args.window)
    ckpt = (CheckpointManager(args.checkpoint_dir)
            if args.checkpoint_dir else None)

    start_step = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        snap = ckpt.restore()
        from repro.launch.elastic import reshard_like
        params = reshard_like(snap["params"], p_sh)
        opt_state = OptState(
            jnp.asarray(snap["opt_state"]["step"]),
            reshard_like(snap["opt_state"]["slots"],
                         jax.tree_util.tree_map(lambda l: l.sharding,
                                                opt_state.slots)))
        start_step = int(snap["meta"]["step"])
        print(f"[resume] from step {start_step}")

    # Current selection over the candidate window (micro-batch granularity).
    k_batches = max(int(args.window * args.budget), 1)
    sel_batches = np.arange(k_batches)
    sel_weights = np.full((k_batches,), 1.0 / k_batches, np.float32)

    losses = []
    grad_norms = []
    rounds = []
    t0 = time.perf_counter()
    sel_seconds = 0.0
    window_round = start_step // args.select_every

    for step in range(start_step, args.steps):
        # --- selection round: pick micro-batches from the upcoming window --
        if args.strategy != "full" and step % args.select_every == 0:
            window_round = step // args.select_every
            ts = time.perf_counter()
            if args.strategy == "gradmatch-pb":
                proxies = window_proxies(proxy_fn, params, stream,
                                         window_round, args.window)
                sel = select_window(mesh, proxies, k_batches, args.lam)
                m = np.asarray(sel.mask)
                sel_batches = np.asarray(sel.indices)[m]
                sel_weights = np.asarray(sel.weights)[m]
                rounds.append({"step": step, "err": float(sel.err),
                               "indices": sel_batches.tolist()})
            else:  # random
                rng = np.random.default_rng(args.seed + step)
                sel_batches = rng.choice(args.window, k_batches,
                                         replace=False)
                sel_weights = np.full((k_batches,), 1.0 / k_batches,
                                      np.float32)
            sel_seconds += time.perf_counter() - ts

        # --- one weighted step on one selected micro-batch -----------------
        pick = step % len(sel_batches)
        batch = dict(stream.batch(window_round, int(sel_batches[pick])))
        w = jnp.full((args.micro_batch,),
                     1.0 / args.micro_batch, jnp.float32)
        batch["weights"] = w * (sel_weights[pick] * len(sel_batches))
        with hints.use_rules(mesh, logical_rules(mesh)):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        grad_norms.append(float(metrics["grad_norm"]))

        if ckpt is not None and (step + 1) % args.checkpoint_every == 0:
            ckpt.save(step + 1, {
                "params": params,
                "opt_state": {"step": opt_state.step,
                              "slots": opt_state.slots},
                "meta": {"step": step + 1, **stream.state(step + 1)},
            })

    if ckpt is not None:
        ckpt.wait()
    wall = time.perf_counter() - t0
    report = {
        "arch": args.arch, "strategy": args.strategy,
        "loss_first": float(np.mean(losses[:5])),
        "loss_last": float(np.mean(losses[-5:])),
        "steps": args.steps, "wall_s": round(wall, 2),
        "selection_s": round(sel_seconds, 2),
        "losses": losses, "grad_norms": grad_norms, "rounds": rounds,
    }
    print(report)
    return report


if __name__ == "__main__":
    main()
