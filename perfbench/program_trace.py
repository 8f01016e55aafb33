"""The program's own view of one traced window of a cell: device time by
the scope the program names, idle time by the innermost host span, and
the program's counter markers.

    python3 perfbench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s>

runs the cell's set-up and window as ``run.py --trace 1`` does, and
prints one JSON line: the window reduced as ``xplane.reduce`` reduces it,
and beside that

- ``scope_ms_per_round``: leaf-operation device ms per OMP round by scope
  (``while``, ``conditional`` and ``call`` operations, which enclose
  others, are left out; operations in no scope count as ``unscoped``);
- ``idle_by_innermost``: each idle interval of the first device split at
  the span edges inside it, each piece charged to the innermost
  ``bench.`` or ``repro.`` span that covers it;
- ``span_counts``: the ``repro.`` spans and counter markers that start in
  the window;
- ``per_layer``: ``score_ms.select``, ``column_ms.select`` and
  ``nnls_ms.select`` (device ms per OMP round in ``omp.score``,
  ``omp.column`` and ``omp.nnls``), ``host_gap_ms.select`` (device-idle
  ms per call inside ``repro.`` spans) and ``programs_loaded.select``
  (``repro.count.jax.program_loads`` markers per call).

Like ``run.py`` it needs a TPU.  The program writes its spans, scopes and
markers through ``repro.obs``; a program without them reads nothing
here.

A device operation's scope is the innermost ``<layer>.<part>`` component
(``omp.score``) of its HLO ``op_name``, the path that ``jax.named_scope``
builds at trace time (``jit(omp_select)/while/body/omp.score/...``).  A
TPU trace does not carry the ``op_name``: an ``XLA Ops`` event, as
``jax.profiler.ProfileData`` reads it, is named by its instruction's text
without the metadata (``%pad.33 = f32[5120,1024]{...} pad(...)``) and
has only ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier`` as stats, and no line of the device plane names scopes.
The ``XLA Modules`` event around it names its program
(``jit_omp_select(<fingerprint>)``).  So the ``op_name`` is read from
the programs themselves: the optimized HLO of every program loaded in
the process (``client.live_executables()``, ``metadata={op_name=...}``),
by program and instruction name.  Fusions that the compiler made
without metadata (``slice_select_fusion``, the column-cache write) take
the scope of the instructions they fuse.
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import shutil
import sys
import tempfile
from dataclasses import dataclass

import xplane

PROGRAM_PREFIX = "repro."
MARKER_PREFIX = "repro.count."
LOADS_MARKER = MARKER_PREFIX + "jax.program_loads"
UNSCOPED = "unscoped"
ENCLOSING = frozenset({"while", "conditional", "call"})
ROUND_SCOPES = {"score_ms.select": "omp.score",
                "column_ms.select": "omp.column",
                "nnls_ms.select": "omp.nnls"}
_SCOPE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")

MODULES_LINE = "XLA Modules"
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%(\S+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([^\s,]+)")


def module_op_names(text: str) -> dict:
    """Instruction name -> ``op_name`` in one optimized HLO module's text.
    A fusion that the compiler left without metadata takes the
    ``op_name`` of the scope that most of its fused instructions name."""
    raw, calls, members = {}, {}, {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            members[comp] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        members[comp].append(name)
        op = _OP_NAME.search(line)
        if op:
            raw[name] = op.group(1)
        else:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    out = dict(raw)
    for name, comp in calls.items():
        named = [raw[i] for i in members.get(comp, ()) if scope_of(
            raw.get(i, ""))]
        if named:
            votes = [scope_of(op) for op in named]
            best = max(votes, key=votes.count)
            out[name] = named[votes.index(best)]
    return out


def hlo_op_names(executables) -> dict:
    """(program name, instruction name) -> ``op_name``, from the optimized
    HLO of loaded programs (``client.live_executables()``)."""
    out = {}
    for ex in executables:
        for module in ex.hlo_modules():
            for instr, op in module_op_names(module.to_string()).items():
                out[(module.name, instr)] = op
    return out


def instruction_name(event_name: str) -> str:
    """``pad.33`` from a trace op named ``%pad.33 = f32[...] pad(...)``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def program_name(event_name: str) -> str:
    """``jit_omp_select`` from ``jit_omp_select(15388027131515875373)``."""
    return event_name.split("(", 1)[0]


def scoped_ops(modules: list, ops: list, op_names: dict) -> list:
    """Device events ``(start_ns, duration_ns, kind, scope)`` from ``ops``
    ``(start_ns, duration_ns, name)``, each looked up in the program of
    the ``modules`` event ``(start_ns, duration_ns, name)`` it starts in."""
    spans = sorted((s, s + d, program_name(n)) for s, d, n in modules)
    starts = [s for s, _, _ in spans]
    out = []
    for s, d, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        program = spans[i][2] if i >= 0 and s < spans[i][1] else None
        op_name = op_names.get((program, instruction_name(name)))
        out.append((s, d, xplane.op_kind(name),
                    scope_of(op_name) if op_name else None))
    return out


def scope_of(op_name: str) -> str | None:
    """The innermost scope in an HLO ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if _SCOPE.match(part):
            return part
    return None


def span_name(name: str) -> str:
    """A host span's name without the ``#key=value,...#`` suffix that a
    ``TraceAnnotation`` with arguments may carry."""
    return name.split("#", 1)[0]


def read_trace(path: str, op_names: dict) -> tuple[dict, list]:
    """(device events by plane, host spans) from one trace file.  Device
    events are ``(start_ns, duration_ns, kind, scope)``, the scope found
    through ``op_names`` (``hlo_op_names``); host spans are the
    ``bench.`` and ``repro.`` ones, ``(start_ns, duration_ns, name)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            lines = {ln.name: [(int(e.start_ns), int(e.duration_ns), e.name)
                               for e in ln.events] for ln in plane.lines}
            device[plane.name] = scoped_ops(
                lines.get(MODULES_LINE, []), lines.get(xplane.OPS_LINE, []),
                op_names)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (int(e.start_ns), int(e.duration_ns), span_name(e.name))
                    for e in line.events
                    if e.name.startswith((xplane.SPAN_PREFIX,
                                          PROGRAM_PREFIX)))
    return device, host


@dataclass
class ProgramTrace:
    base: xplane.Reduced           # the window as run.py reduces it
    scope_seconds: dict            # scope -> leaf-op device seconds
    span_counts: dict              # repro. span or marker -> count
    span_seconds: dict             # repro. span -> host seconds
    idle_by_innermost: dict        # span -> idle seconds
    kinds_by_scope: dict           # scope -> {op kind -> device seconds}

    @property
    def program_traced(self) -> bool:
        """Whether the program wrote spans into the window."""
        return bool(self.span_counts)

    def scope_s(self, scope: str) -> float | None:
        """Device seconds in ``scope``; None where the program wrote no
        span.  A traced program whose scope holds no operation is an
        error: the scope was renamed or left the path."""
        if not self.program_traced:
            return None
        secs = self.scope_seconds.get(scope, 0.0)
        if secs <= 0:
            raise RuntimeError(
                f"no device operation in scope {scope!r} in the window; "
                f"the scopes are {sorted(self.scope_seconds)}")
        return secs

    def unscoped_share(self) -> float:
        total = sum(self.scope_seconds.values())
        return self.scope_seconds.get(UNSCOPED, 0.0) / total if total else 0.0


def _split_idle(spans: list, a: int, b: int) -> dict:
    """Idle interval [a, b) cut at the span edges inside it, each piece
    charged to the shortest span that covers it ("none" where none
    does): span name -> ns."""
    inside = [sp for sp in spans if sp[0] < b and sp[1] > a]
    cuts = sorted({a, b} | {x for s0, s1, _ in inside for x in (s0, s1)
                            if a < x < b})
    out: dict[str, int] = {}
    for x, y in zip(cuts, cuts[1:]):
        cover = [(s1 - s0, name) for s0, s1, name in inside
                 if s0 <= x and y <= s1]
        label = min(cover)[1] if cover else "none"
        out[label] = out.get(label, 0) + (y - x)
    return out


def reduce(device: dict, host: list) -> ProgramTrace:
    """The window of ``xplane.reduce``, with device time by scope, the
    program's span counts and idle time by innermost span."""
    base = xplane.reduce({p: [ev[:3] for ev in evs]
                          for p, evs in device.items()},
                         [h for h in host
                          if h[2].startswith(xplane.SPAN_PREFIX)])
    wins = [(s, s + d) for s, d, n in host if n == xplane.WINDOW_SPAN]
    if wins:
        lo, hi = wins[0]
    else:
        lo = min(ev[0] for evs in device.values() for ev in evs)
        hi = max(ev[0] + ev[1] for evs in device.values() for ev in evs)

    kinds: dict[str, dict] = {}
    for evs in device.values():
        for s, d, kind, *scope in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b > a and kind not in ENCLOSING:
                by_kind = kinds.setdefault(
                    (scope[0] if scope else None) or UNSCOPED, {})
                by_kind[kind] = by_kind.get(kind, 0) + (b - a)

    counts: dict[str, int] = {}
    span_ns: dict[str, int] = {}
    for s, d, n in host:
        if n.startswith(PROGRAM_PREFIX) and lo <= s < hi:
            counts[n] = counts.get(n, 0) + 1
            if not n.startswith(MARKER_PREFIX):
                span_ns[n] = span_ns.get(n, 0) + d

    spans = [(s, s + d, n) for s, d, n in host
             if n != xplane.WINDOW_SPAN and not n.startswith(MARKER_PREFIX)
             and s + d > lo and s < hi]
    first = xplane._merge(xplane._clip(
        [(ev[0], ev[0] + ev[1]) for ev in device[sorted(device)[0]]],
        lo, hi))
    idle: dict[str, int] = {}
    edges = [lo] + [x for ab in first for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            for label, ns in _split_idle(spans, a, b).items():
                idle[label] = idle.get(label, 0) + ns
    return ProgramTrace(
        base=base,
        scope_seconds={sc: sum(k.values()) / 1e9
                       for sc, k in kinds.items()},
        span_counts=counts,
        span_seconds={n: v / 1e9 for n, v in span_ns.items()},
        idle_by_innermost={n: v / 1e9 for n, v in idle.items()},
        kinds_by_scope={sc: {n: v / 1e9 for n, v in k.items()}
                        for sc, k in kinds.items()})


def per_layer(pt: ProgramTrace, work: dict) -> dict:
    """The per-layer numbers of the window, by name; empty where the
    program wrote no span."""
    if not pt.program_traced or not work.get("calls"):
        return {}
    out = {}
    if work.get("rounds_run"):
        for name, scope in ROUND_SCOPES.items():
            out[name] = 1000.0 * pt.scope_s(scope) / work["rounds_run"]
    gap = sum(s for n, s in pt.idle_by_innermost.items()
              if n.startswith(PROGRAM_PREFIX))
    out["host_gap_ms.select"] = 1000.0 * gap / work["calls"]
    out["programs_loaded.select"] = (pt.span_counts.get(LOADS_MARKER, 0)
                                     / work["calls"])
    return out


def summary(pt: ProgramTrace, work: dict, top: int = 10) -> dict:
    rounds = work.get("rounds_run") or 0

    def largest(d):
        return [[n, s] for n, s in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": pt.base.window_s, "busy_s": pt.base.busy_s,
        "calls": work.get("calls"), "rounds_run": rounds,
        "per_layer": per_layer(pt, work),
        "scope_ms_per_round": {
            n: 1000.0 * s / rounds for n, s in pt.scope_seconds.items()
        } if rounds else {},
        "unscoped_share": pt.unscoped_share(),
        "kinds_by_scope": {sc: largest(k)[:5]
                           for sc, k in pt.kinds_by_scope.items()},
        "idle_by_span": pt.base.idle_by_span,
        "idle_by_innermost": largest(pt.idle_by_innermost),
        "span_counts": pt.span_counts,
        "span_ms_per_call": {
            n: 1000.0 * v / work["calls"] for n, v in pt.span_seconds.items()
        } if work.get("calls") else {},
        "device_ops": pt.base.breakdown(top)["device_ops"],
    }


def main(argv=None) -> int:
    import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve_cell(harness.load_benchmark(), args.workload)
    devs = harness.require_chips(cell.chips)
    import jax
    harness.enable_compile_cache()
    loop = harness.loop_for(cell.traffic)
    run = harness.Run(cell, args.seed, args.seconds, True)
    st = loop.setup(run)
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        jax.profiler.start_trace(trace_dir)
        win = loop.window(run, st)
        jax.profiler.stop_trace()
        op_names = hlo_op_names(devs[0].client.live_executables())
        pt = reduce(*read_trace(xplane.find_xplane(trace_dir), op_names))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = {"workload": cell.name, "seed": args.seed,
           "device": devs[0].device_kind, "select_ms": win.metrics.get(
               "select_ms")}
    out.update(summary(pt, win.work))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
