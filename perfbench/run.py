"""Run one cell of the benchmark once, on the chips of this machine.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (inputs and weights from the seed, every shape the cell uses
compiled or read from the compile cache) is timed as ``setup_s``; then
the cell's loop measures for ``--seconds``.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  After the
window the program's state is freed and what the timed path produced is
compared with the configuration's plain reference.  The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import harness  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(run: harness.Run, win: harness.Window, reduced, peaks) -> dict:
    ctx = {"run": run, "window": win, "trace": reduced, "peaks": peaks}
    out = {}
    for m in run.cell.per_layer:
        value = harness.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(harness.SRC, "repro")):
        raise SystemExit(f"perfbench: {harness.SRC}/repro is missing; run "
                         "from a checkout of the repository")
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    devs = harness.require_chips(cell.chips)
    import jax
    harness.enable_compile_cache()
    peaks = harness.load_peaks(devs[0].device_kind)
    loop = harness.loop_for(cell.traffic)
    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace))

    # A persistent-cache hit is reported as a backend compile too; count
    # the hits apart, so that a compile means the compiler ran.
    events = {"compile": 0, "cache_read": 0}
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: events.__setitem__(
            "compile", events["compile"] + 1)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda ev, **_: events.__setitem__(
            "cache_read", events["cache_read"] + 1)
        if ev == "/jax/compilation_cache/cache_hits" else None)

    st = loop.setup(run)
    setup_s = time.perf_counter() - T_START
    in_setup = dict(events)

    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if run.trace \
        else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    win = loop.window(run, st)
    if trace_dir:
        jax.profiler.stop_trace()
    in_window = {k: v - in_setup[k] for k, v in events.items()}
    device = harness.device_info(devs)

    answers = loop.evidence(st)
    del st
    gc.collect()

    reduced = None
    if trace_dir:
        from xplane import find_xplane, read_xplane, reduce
        reduced = reduce(*read_xplane(find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s

    t_check = time.perf_counter()
    checks = loop.check(run, answers)
    check_s = time.perf_counter() - t_check
    correct = all(c.ok for c in checks) and win.failed == 0

    if run.trace:
        metrics = per_layer(run, win, reduced, peaks)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {name: {"value": value, "unit": units[name]}
                   for name, value in win.metrics.items() if name in units}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    print(f"perfbench: {cell.name} seed={args.seed} setup_s={setup_s:.3f} "
          f"window_s={win.seconds:.3f} attempted={win.attempted} "
          f"failed={win.failed} "
          f"compiles_in_setup={in_setup['compile'] - in_setup['cache_read']} "
          f"cache_reads_in_setup={in_setup['cache_read']} "
          f"compiles_in_window={in_window['compile'] - in_window['cache_read']} "
          f"cache_reads_in_window={in_window['cache_read']} "
          f"check_s={check_s:.3f}",
          file=sys.stderr)
    for c in checks:
        print(f"compared {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(harness.result_line(
        correct, win.attempted, win.failed, metrics, device, checks,
        reduced.breakdown() if reduced else None), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
