"""Offline selection-artifact pipeline (DESIGN.md §12).

Precomputes durable anytime-OMP trajectories for a set of pools and
commits them to a content-addressed ``ArtifactStore`` next to the BENCH
files — the MILO-style "solve once, serve any k" fast path.  A serving
deployment pointed at the same store root
(``SelectionService(artifact_store=...)``) then answers gradmatch
requests for these pools at any ``k <= k_max`` in O(1) at submit, rung
``"artifact"``.

Key congruence matters: the artifact is keyed by the pool's
*full-content* digest and the byte-exact SHA of the default target the
registry computes at admission.  The pipeline therefore registers each
pool through a real ``PoolRegistry`` and builds from the registered
entry's ``content_digest``/``target_sum`` — guaranteeing the serving
path's lookup key matches, including the f32 reduction that produced
the target.

``--smoke`` (the CI configuration) builds small pools, then self-checks
the differential guarantee — every artifact slice index-identical to a
live ``omp_select`` at 3 budgets, weights bit-exact to the anytime
session engine — and exits non-zero on violation.

Run:  PYTHONPATH=src python -m repro.launch.build_artifacts --smoke
      PYTHONPATH=src python -m repro.launch.build_artifacts \
          --pools 4 --pool-size 8192 --dim 64 --k-max 512
"""

from __future__ import annotations

import argparse
import pathlib
import time

import numpy as np

from repro.artifacts import ArtifactStore, build_artifact
from repro.core.omp import omp_select, omp_session_start
from repro.launch.cache import enable_compile_cache
from repro.serve.registry import PoolRegistry

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_STORE = REPO_ROOT / "ARTIFACTS_selection"


def build_pools(store: ArtifactStore, pools, k_max: int, lam: float = 0.5,
                eps: float = 1e-10, positive: bool = True) -> list[dict]:
    """Register each (n, d) pool, solve to ``k_max``, commit; returns one
    report dict per pool (ident, build seconds, dims)."""
    registry = PoolRegistry(max_pools=max(len(pools), 1),
                            artifacts=store)
    reports = []
    for g in pools:
        pid = registry.register(g)
        entry = registry.get(pid)
        target = np.asarray(entry.target_sum, np.float32)
        t0 = time.perf_counter()
        key, ident = build_artifact(
            store, np.asarray(g, np.float32), target, k_max, lam=lam,
            eps=eps, positive=positive,
            fingerprint=entry.content_digest)
        dt = time.perf_counter() - t0
        reports.append({"pool_id": pid, "ident": ident, "n": entry.n,
                        "d": entry.d, "k_max": int(k_max),
                        "build_s": dt})
        print(f"build_artifacts,pool={pid},ident={ident},n={entry.n},"
              f"d={entry.d},k_max={k_max},build_s={dt:.2f}", flush=True)
    return reports


def _selfcheck(store: ArtifactStore, pools, reports, lam, eps,
               positive) -> bool:
    """Differential guarantee on every built artifact at 3 k-slices."""
    from repro.artifacts import artifact_key_for

    ok = True
    for g, rep in zip(pools, reports):
        g = np.asarray(g, np.float32)
        import jax.numpy as jnp
        target = np.asarray(jnp.sum(jnp.asarray(g), axis=0), np.float32)
        key = artifact_key_for(g, target, lam, eps, positive)
        art = store.get(key)
        if art is None:
            print(f"build_artifacts,selfcheck={rep['ident']},"
                  f"error=unloadable", flush=True)
            ok = False
            continue
        k_max = rep["k_max"]
        for k in sorted({1, k_max // 2, k_max}):
            idx, w, mask, err = art.slice(k)
            li, lw, lm, _ = omp_select(g, target, k, lam=lam, eps=eps,
                                       positive=positive)
            sess = omp_session_start(g, target, k, lam=lam, eps=eps,
                                     positive=positive)
            same = (np.array_equal(idx, np.asarray(li))
                    and np.array_equal(mask, np.asarray(lm))
                    and np.array_equal(w, np.asarray(sess.weights))
                    and np.allclose(w, np.asarray(lw), rtol=1e-4,
                                    atol=1e-5))
            print(f"build_artifacts,selfcheck={rep['ident']},k={k},"
                  f"ok={same}", flush=True)
            ok &= same
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", default=str(DEFAULT_STORE),
                    help="artifact store root (default: next to BENCH "
                         "files)")
    ap.add_argument("--pools", type=int, default=2)
    ap.add_argument("--pool-size", type=int, default=8192)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--k-max", type=int, default=512)
    ap.add_argument("--lam", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gc", action="store_true",
                    help="mark-then-sweep the store after building")
    ap.add_argument("--smoke", action="store_true",
                    help="small pools + differential self-check (CI)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        args.pools = min(args.pools, 2)
        args.pool_size = min(args.pool_size, 512)
        args.dim = min(args.dim, 32)
        args.k_max = min(args.k_max, 48)

    rng = np.random.default_rng(args.seed)
    pools = [rng.standard_normal(
        (args.pool_size, args.dim)).astype(np.float32)
        for _ in range(args.pools)]
    store = ArtifactStore(args.store)
    reports = build_pools(store, pools, args.k_max, lam=args.lam)
    ok = True
    if args.smoke:
        ok = _selfcheck(store, pools, reports, args.lam, 1e-10, True)
    if args.gc:
        swept = store.gc()
        print(f"build_artifacts,gc_objects={swept['objects_swept']},"
              f"gc_tmp={swept['tmp_swept']}", flush=True)
    print(f"build_artifacts,store={args.store},"
          f"artifacts={store.stats()['artifacts']},"
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
