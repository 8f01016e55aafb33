import os
# CPU-only by design: 512 placeholder host devices, pinned to the CPU
# platform so that neither this process nor the per-cell children it
# spawns ever takes an accelerator.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder CPU devices build the production meshes
((16,16) single-pod, (2,16,16) multi-pod); each cell's step function is
jitted with explicit in/out shardings, ``.lower().compile()`` must succeed,
and the compiled artifact yields

  - ``memory_analysis()``   -> bytes-per-device (proves it fits in 16 GB),
  - ``cost_analysis()``     -> HLO FLOPs / bytes for the roofline terms,
  - partitioned-HLO parse   -> collective operand bytes + schedule.

Usage::

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
      --shape train_4k [--multi-pod] [--microbatches 4] [--out out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

``--all`` runs every applicable cell in a fresh subprocess each (compile
state isolation) and writes one JSON per cell under
``benchmarks/artifacts/dryrun/<mesh>/``.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, applicable_shapes, get_config
from repro.configs.base import ModelConfig, ShapeConfig
from repro.distributed import hints
from repro.distributed.sharding import (logical_rules, param_shardings)
from repro.launch import specs as specs_lib
from repro.launch.cache import enable_compile_cache
from repro.launch.hlo_analysis import (collective_bytes,
                                       collective_bytes_weighted,
                                       roofline_terms)
from repro.launch.mesh import make_production_mesh
from repro.models import lm as lm_lib
from repro.optim import OptState, sgd
from repro.train.steps import lm_train_step_fn

ARTIFACT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    "benchmarks", "artifacts", "dryrun")


def _repl(mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Cell builders: (fn, example_args, in_shardings, out_shardings, donate)
# ---------------------------------------------------------------------------

def build_train(cfg: ModelConfig, shape: ShapeConfig, mesh,
                microbatches: int, fsdp: bool = True):
    opt = sgd(0.01, momentum=0.9, weight_decay=5e-4)
    raw = lm_train_step_fn(cfg, opt, microbatches=microbatches)

    params_sds = specs_lib.param_specs_shapes(cfg)
    opt_sds = jax.eval_shape(opt.init, params_sds)
    batch_sds = specs_lib.input_specs(cfg, shape)

    p_sh = param_shardings(cfg, params_sds, mesh, fsdp=fsdp)
    o_sh = OptState(
        _repl(mesh),
        None if opt_sds.slots is None else param_shardings(
            cfg, opt_sds.slots, mesh, fsdp=fsdp))
    b_sh = specs_lib.batch_shardings(mesh, batch_sds)
    metrics_sh = {"ce": _repl(mesh), "aux": _repl(mesh), "loss": _repl(mesh)}

    return (raw, (params_sds, opt_sds, batch_sds),
            (p_sh, o_sh, b_sh), (p_sh, o_sh, metrics_sh), (0, 1))


def build_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh):
    params_sds = specs_lib.param_specs_shapes(cfg)
    batch_sds = specs_lib.input_specs(cfg, shape)
    p_sh = param_shardings(cfg, params_sds, mesh, fsdp=False)
    b_sh = specs_lib.batch_shardings(mesh, batch_sds)

    if cfg.encoder_only:
        # Encoder "prefill" = full-sequence logits (per-frame units).
        def fn(params, batch):
            h, _, _ = lm_lib.forward(cfg, params, batch.get("tokens"),
                                     embeds=batch.get("embeds"), mode="train")
            return lm_lib._head_out(cfg, params, h)

        out_sds = jax.eval_shape(fn, params_sds, batch_sds)
        out_sh = NamedSharding(mesh, P(("pod", "data") if "pod" in
                                       mesh.axis_names else "data", None,
                                       "model"))
        return fn, (params_sds, batch_sds), (p_sh, b_sh), out_sh, ()

    def fn(params, batch):
        return lm_lib.prefill_step(cfg, params, batch.get("tokens"),
                                   embeds=batch.get("embeds"),
                                   vision=batch.get("vision"))

    logits_sds, states_sds = jax.eval_shape(fn, params_sds, batch_sds)
    dpe = ("pod", "data") if "pod" in mesh.axis_names else "data"
    logits_sh = NamedSharding(mesh, P(dpe, "model"))
    states_sh = specs_lib.state_shardings(cfg, mesh, states_sds)
    return fn, (params_sds, batch_sds), (p_sh, b_sh), (logits_sh, states_sh
                                                       ), ()


def build_decode(cfg: ModelConfig, shape: ShapeConfig, mesh):
    params_sds = specs_lib.param_specs_shapes(cfg)
    batch_sds = specs_lib.input_specs(cfg, shape)
    states_sds = specs_lib.decode_state_specs(cfg, shape)
    seq_shard = shape.global_batch == 1

    # Weight-gathered decode for archs whose TP-sharded weights alone
    # crowd out the KV cache (llama-90b: 180 GB bf16 / 16-way TP = 11 GB
    # of a 16 GB chip).  Sharding weights over data x model and gathering
    # per layer trades ICI for HBM — the standard throughput-decode
    # arrangement for batch-128 serving.
    tp = mesh.shape["model"]
    params_gib_tp = cfg.param_count() * 2 / tp / 2**30
    fsdp = params_gib_tp > 8.0
    p_sh = param_shardings(cfg, params_sds, mesh, fsdp=fsdp)
    b_sh = specs_lib.batch_shardings(mesh, batch_sds, seq_shard=seq_shard)
    s_sh = specs_lib.state_shardings(cfg, mesh, states_sds,
                                     seq_shard=seq_shard)
    dpe = ("pod", "data") if "pod" in mesh.axis_names else "data"
    logits_sh = NamedSharding(
        mesh, P(dpe if shape.global_batch > 1 else None, "model"))

    def fn(params, states, batch):
        tokens = batch.get("tokens")
        if tokens is None:  # audio decode is skipped upstream; guard anyway
            raise ValueError("decode requires tokens")
        return lm_lib.decode_step(cfg, params, states, tokens, batch["pos"])

    return (fn, (params_sds, states_sds, batch_sds),
            (p_sh, s_sh, b_sh), (logits_sh, s_sh), (1,))


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               microbatches: int):
    if shape.kind == "train":
        return build_train(cfg, shape, mesh, microbatches)
    if shape.kind == "prefill":
        return build_prefill(cfg, shape, mesh)
    return build_decode(cfg, shape, mesh)


def analytic_memory_gib(cfg: ModelConfig, shape: ShapeConfig, mesh,
                        microbatches: int) -> dict:
    """Coarse per-chip HBM accounting, independent of the CPU backend.

    XLA:CPU lowers every bf16 dot as convert->f32-dot, so the CPU-measured
    temps systematically overstate what a TPU (native-bf16 MXU) allocates.
    This analytic table is the cross-check for the fits-in-16GB verdict;
    the measured numbers are still reported verbatim.
    """
    n_chips = mesh.size
    tp = mesh.shape["model"]
    dp = n_chips // tp
    n_params = cfg.param_count()
    d = {"params_gib": n_params * 2 / 2**30,
         "per_chip": {}}
    pc = d["per_chip"]
    if shape.kind == "train":
        shard = n_chips  # fsdp: model x data
        pc["params"] = n_params * 2 / shard
        pc["momentum"] = n_params * 4 / shard
        pc["grads_f32"] = n_params * 4 / shard
        tokens_chip = shape.tokens // (dp * microbatches)
        # remat superblock carries + one layer's working set + f32 logits
        pc["act_carries"] = cfg.n_superblocks * tokens_chip * cfg.d_model * 2
        pc["logits_f32"] = tokens_chip * cfg.padded_vocab // tp * 4
    else:
        w_shard = n_chips if (n_params * 2 / tp / 2**30) > 8.0 else tp
        pc["params"] = n_params * 2 / w_shard
        # KV caches / recurrent states: states shard over data x model
        # (heads or head_dim fallback), i.e. ~n_chips-way.
        state_bytes = 0
        for kind in cfg.layer_types_in_order():
            if kind in ("attn", "global", "shared_attn"):
                s_eff = shape.seq_len
            elif kind == "local":
                s_eff = min(cfg.sliding_window or shape.seq_len,
                            shape.seq_len)
            else:   # recurrent: O(1) state per head — negligible
                s_eff = 0
            state_bytes += (2 * shape.global_batch * s_eff
                            * cfg.kv_dim * 2)
        pc["kv_states"] = state_bytes / n_chips
        tokens_chip = max(shape.tokens // dp, shape.seq_len // dp) \
            if shape.kind == "prefill" else shape.global_batch
        pc["activations"] = tokens_chip * cfg.d_model * 2 * 4  # ~4 live
    pc = {k: round(v / 2**30, 3) for k, v in pc.items()}
    d["per_chip"] = pc
    d["per_chip_total_gib"] = round(sum(pc.values()), 2)
    return d


# ---------------------------------------------------------------------------
# One cell: lower + compile + analyse
# ---------------------------------------------------------------------------

def _compile_cell(cfg, shape, mesh, microbatches):
    # The rules context must wrap build_cell too: build_prefill/build_decode
    # run jax.eval_shape over the step fn and jax CACHES that jaxpr — a
    # trace taken outside the context would be reused by .lower() with the
    # hints silently dropped (found the hard way; see EXPERIMENTS §Perf).
    with hints.use_rules(mesh, logical_rules(mesh)):
        fn, args, in_sh, out_sh, donate = build_cell(cfg, shape, mesh,
                                                     microbatches)
        t0 = time.perf_counter()
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    return {
        "compiled": compiled,
        "lower_s": t_lower, "compile_s": t_compile,
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": collective_bytes(hlo),
        "coll_weighted": collective_bytes_weighted(hlo),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatches: int = 0) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    if not microbatches:  # adaptive: ~2 sequences per chip per microbatch
        dp = n_chips // mesh.shape["model"]
        microbatches = max(shape.global_batch // (2 * dp), 1)

    # --- pass 1: the PRODUCTION module (scan + remat + microbatching).
    # This is the compile proof + the memory analysis that must fit HBM.
    prod = _compile_cell(cfg, shape, mesh,
                         microbatches if shape.kind == "train" else 1)
    ma = prod["compiled"].memory_analysis()

    # --- pass 2+3: cost accounting.  XLA's cost_analysis counts while-loop
    # bodies ONCE regardless of trip count, so the scanned module's numbers
    # are depth-independent.  Superblocks are homogeneous by construction,
    # so two *unrolled shallow* variants (L=1, L=2) give the exact marginal
    # per-superblock cost; totals extrapolate linearly:
    #     cost(L) = cost(1) + (L-1) * (cost(2) - cost(1)).
    # Residual in-loop work (SSD/mLSTM cross-chunk state carry, sLSTM
    # recurrence) is elementwise-dominated — see DESIGN.md.
    L = cfg.n_superblocks
    # Large flash tiles in the cost modules: same math/FLOPs, far fewer
    # unrolled tile bodies (compile time) — tile size only affects memory,
    # which pass 1 measures.
    cost_cfg = cfg.replace(n_layers=0, unroll_scan=True,
                           flash_block_q=8192, flash_block_kv=8192)
    c1 = _compile_cell(cost_cfg.replace(n_superblocks=1), shape, mesh, 1)
    c2 = _compile_cell(cost_cfg.replace(n_superblocks=2), shape, mesh, 1)

    # Marginal per-superblock deltas are clamped at 0: XLA occasionally
    # hoists/CSEs an op differently between the L=1 and L=2 modules
    # (e.g. zamba2's shared-attention weight gather), which would otherwise
    # produce a negative slope.
    def extrap(key):
        return c1[key] + (L - 1) * max(c2[key] - c1[key], 0.0)

    flops = extrap("flops")
    bytes_accessed = extrap("bytes")
    # Collectives come from the PRODUCTION module with while-loop trip
    # counts applied (hlo_analysis.collective_bytes_weighted): unlike the
    # L1/L2 modules, the production module's GSPMD layout decisions are
    # the ones a real run executes (validated within 7% of a fully
    # unrolled compile for gemma-2b x train_4k).
    wc = prod["coll_weighted"]
    coll_bytes_total = wc.total_bytes
    coll_counts = dict(wc.counts)
    coll_op_bytes = dict(wc.operand_bytes)
    t_lower, t_compile = prod["lower_s"], prod["compile_s"]
    terms = roofline_terms(flops, bytes_accessed, coll_bytes_total, n_chips)

    # MODEL_FLOPS: 6*N_active*D for train (fwd+bwd), 2*N_active*D inference.
    tokens = (shape.tokens if shape.kind != "decode"
              else shape.global_batch)  # decode: one token per sequence
    per_tok = cfg.flops_per_token()
    model_flops = per_tok * tokens * (1.0 if shape.kind == "train"
                                      else 1.0 / 3.0)
    hlo_flops_global = flops * n_chips
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "microbatches": (microbatches if
                                             shape.kind == "train" else 1),
        "ok": True,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            # XLA:CPU ignores buffer donation (alias=0); on TPU the donated
            # params/opt/caches alias in-place, so the honest per-device
            # peak is max(args, outputs) + temps.
            "peak_device_bytes": (max(ma.argument_size_in_bytes,
                                      ma.output_size_in_bytes)
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
            "analytic": analytic_memory_gib(cfg, shape, mesh, microbatches),
        },
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "collectives": {"counts": coll_counts,
                        "operand_bytes": coll_op_bytes,
                        "total_bytes": coll_bytes_total,
                        "production_module_once_counted":
                            prod["coll"].as_dict()},
        "roofline": terms,
        "model_flops_global": model_flops,
        "useful_flops_ratio": (model_flops / hlo_flops_global
                               if hlo_flops_global else None),
        "params_total": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
    }
    return result


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _cell_out_path(arch: str, shape: str, multi_pod: bool) -> str:
    mesh_dir = "2x16x16" if multi_pod else "16x16"
    d = os.path.join(ARTIFACT_DIR, mesh_dir)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--out")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.all:
        from repro.configs import ARCH_IDS
        failures = []
        for arch in ARCH_IDS:
            for shape in applicable_shapes(get_config(arch)):
                out = _cell_out_path(arch, shape.name, args.multi_pod)
                if args.skip_existing and os.path.exists(out):
                    with open(out) as f:
                        if json.load(f).get("ok"):
                            print(f"[skip] {arch} x {shape.name}")
                            continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape.name,
                       "--microbatches", str(args.microbatches),
                       "--out", out]
                if args.multi_pod:
                    cmd.append("--multi-pod")
                print(f"[run ] {arch} x {shape.name} "
                      f"({'2x16x16' if args.multi_pod else '16x16'})",
                      flush=True)
                r = subprocess.run(cmd, env={**os.environ,
                                             "PYTHONPATH": "src"})
                if r.returncode != 0:
                    failures.append((arch, shape.name))
        print(f"\n{'FAILURES: ' + str(failures) if failures else 'all ok'}")
        sys.exit(1 if failures else 0)

    try:
        result = run_cell(args.arch, args.shape, args.multi_pod,
                          args.microbatches)
    except Exception:
        result = {"arch": args.arch, "shape": args.shape,
                  "mesh": "2x16x16" if args.multi_pod else "16x16",
                  "ok": False, "error": traceback.format_exc()}
    out = args.out or _cell_out_path(args.arch, args.shape, args.multi_pod)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    if result["ok"]:
        m = result["memory"]
        print(f"{args.arch} x {args.shape}: OK  "
              f"peak/device={m['peak_device_bytes']/2**30:.2f} GiB  "
              f"flops/chip={result['hlo_flops_per_chip']:.3g}  "
              f"coll={result['collectives']['total_bytes']/2**30:.3f} GiB  "
              f"dominant={result['roofline']['dominant']}")
    else:
        print(result["error"], file=sys.stderr)
        print(f"{args.arch} x {args.shape}: FAILED", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
