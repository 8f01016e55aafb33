"""Run the system's main paths once on a TPU, and check what comes out.

    python chip_smoke.py                # one chip: phases 1-5 below
    python chip_smoke.py --four-chips   # four chips: the sharded paths only

The default run drives each path through the entry point a user calls:

1. device: JAX's default device is a TPU and the kernels dispatch to
   compiled Pallas (``ops.active_mode() == "pallas"``);
2. selection: ``core.selection.select`` for ``gradmatch``,
   ``gradmatch-stream`` and ``craig-lazy`` on a seeded 8192 x 512 pool.
   Each objective must lie within 1% of the same call made in a CPU-only
   child process (``JAX_PLATFORMS=cpu``, f32 ``ref`` kernels), with as
   many rows selected; ``craig-lazy`` must pick exactly what dense
   ``craig`` picks at n=4096 on the chip;
3. serving: a ``SelectionService`` over two 8192 x 512 pools answers
   eight requests from two tenants and extends one session k=256 -> 512.
   Every ticket must come back ``done`` and ``certified`` with its
   objective within 1% of a single-request solve's (how many are
   index-identical is printed), and the extension must pick exactly what
   a one-shot solve picks;
4. LM training at full width: ``launch/train.py`` on xlstm-1.3b for six
   steps with two gradmatch-pb selection rounds and finite losses;
5. the paper's Algorithm 1: ``AdaptiveTrainer`` with per-class gradmatch
   at budget 0.1 on a 50,000-row, 10-class pool, two selection epochs.

``--four-chips`` runs FSDP gemma-2b training through the sharded OMP
(whose selection must match single-device gradmatch within 1% on the
same window proxies) and ``gradmatch-partitioned`` at P=4 across the
chips (set-identical to the single-device vmap solve).

Each phase prints its XLA compile seconds and the rest of its wall time.
A failed check raises, so the script exits non-zero and never prints its
last line, which on success is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
if not os.path.isdir(os.path.join(SRC, "repro")):
    raise SystemExit(f"chip_smoke: {SRC}/repro is missing; run this script "
                     "from a checkout of the repository")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.core import gradmatch as gm_lib  # noqa: E402
from repro.core import partition as part_lib  # noqa: E402
from repro.core import selection as sel_lib  # noqa: E402
from repro.core.omp import omp_select  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

# The shape behind the committed selection_* benchmark rows.
SELECTION = dict(n=8192, d=512, k=512, k_craig=819, chunk=1024,
                 n_parity=4096, k_parity=409, seed=0)
SERVING = dict(n=8192, d=512, pools=2, requests=8, tenants=2, k=256,
               k_extend=512, seed=1)
LM_TRAIN = dict(arch="xlstm-1.3b", steps=6, select_every=3, window=16,
                micro_batch=4, seq_len=128)
TRAINER = dict(n=50_000, n_val=5_000, dim=64, classes=10, budget=0.1,
               epochs=2, seed=0)
FSDP_TRAIN = dict(arch="gemma-2b", steps=4, select_every=2, window=16,
                  micro_batch=4, seq_len=128, data=4)
PARTITIONED = dict(n=8192, d=512, k=512, parts=4, seed=0)

OBJECTIVE_BAND = 0.01     # |err_chip / err_reference - 1|
REFERENCE_TIMEOUT_S = 900


class SmokeFailure(RuntimeError):
    """A check of what the chip computed did not hold."""


def check(failures: list, ok: bool, what: str) -> None:
    if not ok:
        failures.append(what)


def raise_if(failures: list, phase: str) -> None:
    if failures:
        raise SmokeFailure(f"{phase}: " + "; ".join(failures))


def compile_seconds() -> float:
    """XLA backend compile seconds so far in this process, persistent-cache
    reads included, as JAX reports them (``repro.obs``)."""
    return obs.counters().get("jax.compile_s", 0.0)


def run_phase(name: str, fn):
    t0, c0 = time.perf_counter(), compile_seconds()
    out = fn()
    wall = time.perf_counter() - t0
    compile_s = compile_seconds() - c0
    print(f"[phase] {name}: wall_s={wall:.2f} compile_s={compile_s:.2f} "
          f"run_s={wall - compile_s:.2f}", flush=True)
    return out


def backend_info() -> dict:
    return {"platform": jax.devices()[0].platform,
            "mode": ops.active_mode()}


# ---------------------------------------------------------------------------
# phase 1: the device
# ---------------------------------------------------------------------------

def check_device(count: int = 1) -> jax.Device:
    """The default device must be a TPU (at least ``count`` of them) with
    the Pallas kernels compiled for it; prints what JAX reports."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's default "
                         f"device is {dev.platform!r}")
    if ops.active_mode() != "pallas":
        raise SystemExit(f"chip_smoke: kernels dispatch to "
                         f"{ops.active_mode()!r}, not compiled Pallas")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU devices, found "
                         f"{len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    print(json.dumps({"device_kind": dev.device_kind, "count": len(devs),
                      "jax": jax.__version__, "libtpu": libtpu,
                      "memory_stats": dev.memory_stats()}), flush=True)
    return dev


# ---------------------------------------------------------------------------
# phase 2: selection against a CPU f32 reference
# ---------------------------------------------------------------------------

def make_pool(n: int, d: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, d),
                                                       dtype=np.float32)


def _strategies(spec: dict):
    return (("gradmatch", spec["k"], {}),
            ("gradmatch-stream", spec["k"], {"chunk_size": spec["chunk"]}),
            ("craig-lazy", spec["k_craig"], {}))


def summarize(res) -> dict:
    mask = np.asarray(res.mask)
    return {"err": float(res.err), "selected": int(mask.sum()),
            "indices": np.asarray(res.indices)[mask].tolist()}


def run_selections(spec: dict) -> dict:
    """Every strategy of ``_strategies`` on the seeded pool, through
    ``select``; runs the same way on the chip and in the CPU child."""
    pool = jnp.asarray(make_pool(spec["n"], spec["d"], spec["seed"]))
    key = jax.random.PRNGKey(spec["seed"])
    return {name: summarize(sel_lib.select(name, key, pool, k, **kw))
            for name, k, kw in _strategies(spec)}


def cpu_reference_main(spec: dict) -> None:
    """The reference side of phase 2, run in a child that never sees the
    chip: plain f32 ``ref`` kernels on the CPU."""
    info = backend_info()
    if info != {"platform": "cpu", "mode": "ref"}:
        raise SystemExit(f"chip_smoke reference: expected cpu/ref, got "
                         f"{info}")
    print(json.dumps({**info, "results": run_selections(spec)}))


def start_cpu_reference(spec: dict) -> subprocess.Popen:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--cpu-reference",
         json.dumps(spec)], stdout=subprocess.PIPE, text=True, env=env)


def collect_cpu_reference(proc: subprocess.Popen, timeout: float) -> dict:
    out, _ = proc.communicate(timeout=timeout)
    if proc.returncode != 0:
        raise SmokeFailure(f"CPU reference child exited {proc.returncode}")
    return json.loads(out.strip().splitlines()[-1])


def phase_selection(spec: dict = SELECTION,
                    timeout: float = REFERENCE_TIMEOUT_S) -> dict:
    proc = start_cpu_reference(spec)        # runs while the chip works
    try:
        mine = run_selections(spec)
        ref = collect_cpu_reference(proc, timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    side = backend_info()
    print(f"selection: device side {side['platform']}/{side['mode']}, "
          f"reference side {ref['platform']}/{ref['mode']}", flush=True)
    failures: list = []
    ratios = {}
    for name, _, _ in _strategies(spec):
        got, want = mine[name], ref["results"][name]
        ratio = got["err"] / want["err"]
        ratios[name] = ratio
        print(json.dumps({
            "strategy": name, "err": got["err"], "err_ref": want["err"],
            "ratio": ratio, "selected": got["selected"],
            "selected_ref": want["selected"],
            "same_rows": len(set(got["indices"]) & set(want["indices"]))}),
            flush=True)
        check(failures, abs(ratio - 1.0) <= OBJECTIVE_BAND,
              f"{name} objective ratio {ratio:.6f} outside 1 +- "
              f"{OBJECTIVE_BAND}")
        check(failures, got["selected"] == want["selected"],
              f"{name} selected {got['selected']} rows, reference "
              f"{want['selected']}")

    # The repo's lazy-greedy contract, on this device: craig-lazy picks
    # exactly what the dense oracle picks.
    pool = jnp.asarray(make_pool(spec["n_parity"], spec["d"], spec["seed"]))
    key = jax.random.PRNGKey(spec["seed"])
    dense = summarize(sel_lib.select("craig", key, pool, spec["k_parity"]))
    lazy = summarize(sel_lib.select("craig-lazy", key, pool,
                                    spec["k_parity"]))
    same = dense["indices"] == lazy["indices"]
    print(json.dumps({"craig_parity_n": spec["n_parity"],
                      "k": spec["k_parity"], "index_identical": same,
                      "selected": lazy["selected"]}), flush=True)
    check(failures, same, "craig-lazy differs from dense craig")
    raise_if(failures, "selection")
    return ratios


# ---------------------------------------------------------------------------
# phase 3: serving
# ---------------------------------------------------------------------------

def phase_serving(spec: dict = SERVING) -> dict:
    from repro.serve import SelectionService

    svc = SelectionService(max_batch=32)
    pools = {}
    for p in range(spec["pools"]):
        g = make_pool(spec["n"], spec["d"], spec["seed"] + p)
        pools[svc.register_pool(g)] = jnp.asarray(g)
    ids = list(pools)
    tickets = [svc.submit(ids[i % len(ids)], k=spec["k"],
                          tenant=f"tenant-{i % spec['tenants']}")
               for i in range(spec["requests"])]
    svc.drain()
    failures: list = []
    singles = {pid: omp_select(g, jnp.sum(g, axis=0), k=spec["k"])
               for pid, g in pools.items()}
    identical = 0
    for i, t in enumerate(tickets):
        check(failures, t.status == "done" and t.degradation == "certified",
              f"ticket {i}: {t.status}/{t.degradation} {t.error or ''}")
        if t.status != "done":
            continue
        idx, _, mask, err = singles[t.request.pool_id]
        identical += int(
            np.array_equal(np.asarray(t.result.indices), np.asarray(idx))
            and np.array_equal(np.asarray(t.result.mask), np.asarray(mask)))
        ratio = float(t.result.err) / float(err)
        check(failures, abs(ratio - 1.0) <= OBJECTIVE_BAND,
              f"ticket {i} objective {ratio:.6f}x a single-request solve's")

    pid0 = ids[0]
    sid, _ = svc.open_session(pid0, k=spec["k"], tenant="tenant-0")
    ext = svc.extend_session(sid, spec["k_extend"])
    g0 = pools[pid0]
    one_idx, _, one_mask, _ = omp_select(g0, jnp.sum(g0, axis=0),
                                         k=spec["k_extend"])
    extension_ok = (np.array_equal(np.asarray(ext.indices),
                                   np.asarray(one_idx))
                    and np.array_equal(np.asarray(ext.mask),
                                       np.asarray(one_mask)))
    check(failures, extension_ok,
          f"extension k={spec['k']}->{spec['k_extend']} differs from a "
          "one-shot solve")
    stats = svc.stats()["scheduler"]
    report = {"tickets": [f"{t.status}/{t.degradation}/b{t.batched_with}"
                          for t in tickets],
              "batches_run": stats["batches_run"],
              "identical_to_single": identical,
              "extension": f"k={spec['k']}->{spec['k_extend']}",
              "extension_identical": extension_ok,
              "extension_err": float(ext.err)}
    print(json.dumps(report), flush=True)
    raise_if(failures, "serving")
    return report


# ---------------------------------------------------------------------------
# phases 4 and 4-chip: LM training through launch/train.py
# ---------------------------------------------------------------------------

def _train_argv(spec: dict, smoke: bool) -> list:
    argv = ["--arch", spec["arch"], "--strategy", "gradmatch-pb",
            "--steps", str(spec["steps"]),
            "--select-every", str(spec["select_every"]),
            "--window", str(spec["window"]),
            "--micro-batch", str(spec["micro_batch"]),
            "--seq-len", str(spec["seq_len"])]
    if spec.get("data", 1) > 1:
        argv += ["--fsdp", "--mesh-data", str(spec["data"])]
    return argv + (["--smoke"] if smoke else [])


def _peak_bytes() -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def phase_lm_train(spec: dict = LM_TRAIN, smoke: bool = False) -> dict:
    from repro.launch import train

    rep = train.main(_train_argv(spec, smoke))
    rounds = math.ceil(spec["steps"] / spec["select_every"])
    failures: list = []
    check(failures, len(rep["losses"]) == spec["steps"],
          f"{len(rep['losses'])} steps taken, asked {spec['steps']}")
    check(failures, all(math.isfinite(x) for x in rep["losses"]),
          f"non-finite loss in {rep['losses']}")
    check(failures, all(math.isfinite(x) for x in rep["grad_norms"]),
          f"non-finite gradient norm in {rep['grad_norms']}")
    check(failures, len(rep["rounds"]) == rounds,
          f"{len(rep['rounds'])} selection rounds, expected {rounds}")
    print(json.dumps({"arch": rep["arch"], "wall_s": rep["wall_s"],
                      "selection_s": rep["selection_s"],
                      "losses": rep["losses"],
                      "grad_norms": rep["grad_norms"],
                      "rounds": rep["rounds"],
                      "peak_bytes_in_use": _peak_bytes()}), flush=True)
    raise_if(failures, "lm-train")
    return rep


def phase_fsdp_train(spec: dict = FSDP_TRAIN, smoke: bool = False) -> dict:
    """FSDP training through the sharded OMP, then the sharded selection
    of round 0 against single-device gradmatch on the same proxies."""
    from repro.configs import get_config, get_smoke_config
    from repro.data.tokens import TokenStream
    from repro.launch import train
    from repro.launch.mesh import make_host_mesh
    from repro.train.steps import make_lm_proxy_step

    rep = phase_lm_train(spec, smoke)
    args = train.build_argparser().parse_args(_train_argv(spec, smoke))
    cfg = (get_smoke_config(args.arch) if smoke else get_config(args.arch))
    mesh = make_host_mesh(args.mesh_data, args.mesh_model)
    # Round 0 selects with the initial parameters, so its window proxies
    # can be rebuilt exactly from the seed.
    params, _ = train.init_params(cfg, jax.random.PRNGKey(args.seed), mesh,
                                  args.fsdp)
    stream = TokenStream(seed=args.seed, batch_per_shard=args.micro_batch,
                         seq_len=args.seq_len, vocab=cfg.vocab_size,
                         n_shards=args.window)
    proxies = train.window_proxies(make_lm_proxy_step(cfg), params, stream,
                                   0, args.window)
    del params
    k_batches = max(int(args.window * args.budget), 1)
    sharded = train.select_window(mesh, proxies, k_batches, args.lam)
    single = gm_lib.gradmatch(jax.device_put(proxies, jax.devices()[0]),
                              k_batches, lam=args.lam)
    s_sum, one_sum = summarize(sharded), summarize(single)
    ratio = s_sum["err"] / one_sum["err"]
    print(json.dumps({"sharded": s_sum, "single_device": one_sum,
                      "ratio": ratio, "train_round0": rep["rounds"][0]}),
          flush=True)
    failures: list = []
    check(failures, s_sum["indices"] == rep["rounds"][0]["indices"],
          "rebuilt round-0 selection differs from the one training made")
    check(failures, abs(ratio - 1.0) <= OBJECTIVE_BAND,
          f"sharded/single objective ratio {ratio:.6f}")
    raise_if(failures, "fsdp-train")
    return {"ratio": ratio}


def phase_partitioned(spec: dict = PARTITIONED) -> dict:
    """``gradmatch-partitioned`` across the devices (one partition per
    device through ``pmap``) against the single-device vmap solve."""
    pool = jnp.asarray(make_pool(spec["n"], spec["d"], spec["seed"]))
    key = jax.random.PRNGKey(spec["seed"])
    spread = summarize(sel_lib.select("gradmatch-partitioned", key, pool,
                                      spec["k"], partitions=spec["parts"]))
    local = summarize(part_lib.gradmatch_partitioned(
        pool, spec["k"], partitions=spec["parts"], use_pmap=False))
    same = sorted(spread["indices"]) == sorted(local["indices"])
    print(json.dumps({"devices": jax.local_device_count(),
                      "parts": spec["parts"], "set_identical": same,
                      "err_pmap": spread["err"], "err_vmap": local["err"],
                      "selected": spread["selected"]}), flush=True)
    failures: list = []
    check(failures, jax.local_device_count() == spec["parts"],
          f"{jax.local_device_count()} devices for {spec['parts']} parts")
    check(failures, same, "pmap and vmap partitioned selections differ")
    raise_if(failures, "partitioned")
    return {"set_identical": same}


# ---------------------------------------------------------------------------
# phase 5: the paper's Algorithm 1
# ---------------------------------------------------------------------------

def phase_trainer(spec: dict = TRAINER) -> dict:
    from repro.configs.paper import PaperHParams, mlp
    from repro.data.synthetic import Dataset, make_classification
    from repro.train.trainer import AdaptiveTrainer, TrainerConfig

    n = spec["n"]
    ds = make_classification(jax.random.PRNGKey(spec["seed"]),
                             n=n + spec["n_val"], dim=spec["dim"],
                             num_classes=spec["classes"])
    train_ds = Dataset(ds.x[:n], ds.y[:n], ds.num_classes)
    val_ds = Dataset(ds.x[n:], ds.y[n:], ds.num_classes)
    tcfg = TrainerConfig(strategy="gradmatch", budget=spec["budget"],
                         epochs=spec["epochs"],
                         hp=PaperHParams(select_every=1), seed=spec["seed"])
    rep = AdaptiveTrainer(mlp(in_dim=spec["dim"],
                              num_classes=spec["classes"]),
                          tcfg, train_ds, val_ds).run()
    out = {"selection_rounds": rep.selection_rounds,
           "subset_size": rep.subset_size, "final_acc": rep.final_acc,
           "selection_seconds": rep.selection_seconds,
           "wall_seconds": rep.wall_seconds}
    print(json.dumps(out), flush=True)
    failures: list = []
    check(failures, rep.selection_rounds == spec["epochs"],
          f"{rep.selection_rounds} selection rounds in {spec['epochs']} "
          "epochs")
    check(failures, 0 < rep.subset_size <= int(n * spec["budget"]),
          f"subset of {rep.subset_size} rows")
    check(failures, math.isfinite(rep.final_acc), "non-finite accuracy")
    raise_if(failures, "trainer")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, on four chips")
    ap.add_argument("--cpu-reference", metavar="SPEC",
                    help="internal: the CPU reference child of phase 2")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.cpu_reference:
        cpu_reference_main(json.loads(args.cpu_reference))
        return 0

    count = FSDP_TRAIN["data"] if args.four_chips else 1
    dev = run_phase("device", lambda: check_device(count))
    if args.four_chips:
        run_phase("fsdp-train", phase_fsdp_train)
        run_phase("partitioned", phase_partitioned)
    else:
        run_phase("selection", phase_selection)
        run_phase("serving", phase_serving)
        run_phase("lm-train", phase_lm_train)
        run_phase("trainer", phase_trainer)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
