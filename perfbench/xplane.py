"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
device time by operation, and idle gaps labelled by the host span they
fall in.

The reduction works on plain tuples, so a test can hand it a small
synthetic trace: ``device`` maps a device plane's name to its operation
events and ``host`` lists the benchmark's own spans, each event being
``(start_ns, duration_ns, name)`` on the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_kind(name: str) -> str:
    """An operation's kind from its name in a TPU trace, which is the whole
    HLO instruction (``%corr_argmax.50 = (s32[...]) custom-call(...)``):
    the instruction's name without its ``%`` and numeric suffix
    (``corr_argmax``), so that one kind of operation adds up across
    programs and calls."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head) or name


def read_xplane(path: str) -> tuple[dict, list]:
    """(device events by plane, host spans) from one trace file; device
    events are named by ``op_kind``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            device[plane.name] = [
                (int(e.start_ns), int(e.duration_ns), op_kind(e.name))
                for ln in (ops or lines) for e in ln.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.duration_ns), e.name)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return device, host


def _merge(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclass
class Reduced:
    window_s: float
    busy_s: float                          # mean over devices
    busy_by_device: dict
    op_seconds: dict                       # op name -> device seconds
    idle_by_span: dict                     # host span -> idle seconds

    def ops_matching(self, *needles: str) -> float:
        """Device seconds of the operations whose name holds a needle."""
        return sum(s for n, s in self.op_seconds.items()
                   if any(x in n for x in needles))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def reduce(device: dict, host: list) -> Reduced:
    """Busy and idle time inside the benchmark's window span.

    Busy is the union of the intervals in which an operation runs on a
    device.  Each idle gap on the first device is charged to the
    innermost benchmark span that covers its midpoint ("none" where the
    host was in no span)."""
    if not device or not any(device.values()):
        raise ValueError("the trace holds no device operation")
    wins = [(s, s + d) for s, d, n in host if n == WINDOW_SPAN]
    if wins:
        lo, hi = wins[0]
    else:
        lo = min(s for evs in device.values() for s, _, _ in evs)
        hi = max(s + d for evs in device.values() for s, d, _ in evs)
    window_ns = max(hi - lo, 1)
    spans = [(s, s + d, n[len(SPAN_PREFIX):]) for s, d, n in host
             if n != WINDOW_SPAN and s + d > lo and s < hi]

    busy_by_device = {}
    op_ns: dict[str, int] = {}
    merged_by_device = {}
    for plane in sorted(device):
        evs = device[plane]
        merged = _merge(_clip([(s, s + d) for s, d, _ in evs], lo, hi))
        merged_by_device[plane] = merged
        busy_by_device[plane] = sum(b - a for a, b in merged) / 1e9
        for s, d, n in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                op_ns[n] = op_ns.get(n, 0) + (b - a)

    first = merged_by_device[sorted(merged_by_device)[0]]
    idle: dict[str, int] = {}
    edges = [lo] + [x for ab in first for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        cover = [(s1 - s0, name) for s0, s1, name in spans
                 if s0 <= mid < s1]
        label = min(cover)[1] if cover else "none"
        idle[label] = idle.get(label, 0) + (b - a)

    n_dev = len(busy_by_device)
    return Reduced(
        window_s=window_ns / 1e9,
        busy_s=sum(busy_by_device.values()) / n_dev,
        busy_by_device=busy_by_device,
        op_seconds={n: v / 1e9 for n, v in op_ns.items()},
        idle_by_span={n: v / 1e9 for n, v in idle.items()})
