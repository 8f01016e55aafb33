"""What every cell of the benchmark shares: finding its files by name, the
device check, the compile cache, host spans, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (a JSON file of sizes) and a traffic mix (a JSON file under
``perfbench/traffic/``).  The traffic file names the loop
(``perfbench/loops/<loop>.py``) that runs it; each per-layer metric is
``perfbench/metrics/<metric>.py``.  Nothing here knows a cell by name.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
# Inside the checkout, at a fixed path: the path is part of the cache key.
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class NoChip(SystemExit):
    """The run found no accelerator, or fewer chips than the cell asks."""


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------

def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file of the benchmark by its path (metric and loop
    files carry dots and dashes in their names)."""
    name = "perfbench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def resolve_cell(bench: dict, workload: str, root: str = ROOT,
                 bench_dir: str = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return Cell(workload, int(w["chips"]), config, traffic,
                mine(bench["end_to_end"]), mine(bench["per_layer"]))


def loop_for(traffic: dict):
    return load_module(os.path.join(BENCH_DIR, "loops",
                                    traffic["loop"] + ".py"))


def metric_reader(name: str):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def require_chips(count: int) -> list:
    """The TPU devices the cell asks for; exits non-zero without them."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"perfbench: JAX found no device: {e}")
    if devs[0].platform != "tpu":
        raise NoChip(f"perfbench: needs a TPU, but JAX's device is "
                     f"{devs[0].platform!r}")
    if len(devs) < count:
        raise NoChip(f"perfbench: the cell asks for {count} chips, JAX "
                     f"found {len(devs)}")
    return devs[:count]


def enable_compile_cache(path: str = CACHE_DIR) -> None:
    """Cache every program, however small or quick to compile, so that
    only the first run of a cell in a checkout compiles."""
    import jax
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(devs: list) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def load_peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# spans and the run record
# ---------------------------------------------------------------------------

@dataclass
class Run:
    """One invocation: the cell, its seed, and what the window recorded."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    spans: list = field(default_factory=list)     # (name, t0, t1) host s

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around a call into one layer; with ``--trace 1`` it
        is also written into the profiler's trace as ``bench.<name>``."""
        ann = contextlib.nullcontext()
        if self.trace:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, t0, time.perf_counter()))


@dataclass
class Window:
    """What a loop's measured window hands back to ``run.py``."""

    metrics: dict          # end-to-end metric name -> value
    attempted: int
    failed: int
    seconds: float         # length of the measured window, host clock
    work: dict = field(default_factory=dict)   # counts for the readers


@dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def drive(run: Run, loop) -> tuple[Window, list, bool]:
    """Set-up, window and comparison of one run, without the device check,
    the trace or the result line (``run.py`` does those in between)."""
    import gc
    st = loop.setup(run)
    win = loop.window(run, st)
    answers = loop.evidence(st)
    del st
    gc.collect()
    checks = loop.check(run, answers)
    return win, checks, all(c.ok for c in checks) and win.failed == 0


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: list,
                breakdown: Optional[dict] = None) -> str:
    out: dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                       for c in checks}
    return json.dumps(out)
