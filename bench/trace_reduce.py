"""From a profiler trace to device busy time, idle gaps and op times.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation run, named by its HLO text, and the ``XLA Modules`` line one
per program run (``jit_serve_step(<id>)``).  Host and device events share
one clock.  Busy time is the union of the op intervals inside the traced
window, averaged over the devices used; an idle gap is a stretch of the
window in which no op ran, labelled with the harness's span
(``bench.*``) and the innermost host event that covered its middle.

Each op is keyed ``<program>: <op> <result type> <opcode>``, e.g.
``jit_serve_step: %closed_call.13 f32[16,56,128]
custom-call(tpu_custom_call)`` for a Pallas kernel: the program that ran
it, found by time, and its HLO result without layouts.  Control flow
(``while``, ``conditional``, ``call``) wraps other ops and is left out
of the list of top ops.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


WRAPPERS = ("while", "conditional", "call")


def _program(name: str) -> str:
    """``jit_serve_step(123)`` -> ``jit_serve_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_label(text: str) -> tuple[str, str]:
    """(``<op> <result type> <opcode>``, opcode) of an HLO op's text."""
    m = re.match(r"^(%[\w.\-]+) = (.*)$", text, re.S)
    if not m:
        return text[:80], ""
    ident, rest = m.groups()
    depth, i = 0, len(rest)
    for j, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            i = j
            break
    rtype = re.sub(r"\{[^{}]*\}", "", rest[:i])
    rtype = "(tuple)" if rtype.startswith("(") else rtype
    op = re.match(r"[\w\-]*", rest[i + 1:]).group(0)
    if op == "custom-call":
        tgt = re.search(r'custom_call_target="([^"]+)"', rest)
        op += f"({tgt.group(1)})" if tgt else ""
    return f"{ident} {rtype} {op}", op


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _host_events(planes) -> list[tuple[float, float, str]]:
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
    return out


def _label(host, t: float) -> str:
    """The harness span and the innermost host event covering time t."""
    covering = [(b - a, name) for a, b, name in host if a <= t <= b]
    spans = sorted(x for x in covering if x[1].startswith("bench."))
    other = sorted(x for x in covering if not x[1].startswith("bench."))
    span = spans[0][1] if spans else "no bench span"
    return f"{span} / {other[0][1]}" if other else span


def reduce(planes, top: int = 10) -> dict:
    """Busy and idle time, per-program and per-op device time, and the
    longest idle gaps of the traced window.

    ``planes`` is ``ProfileData.planes`` (or objects with the same
    ``name`` / ``lines`` / ``events`` shape).  The window is the host
    span ``bench.window``; times are in seconds."""
    planes = list(planes)
    host = _host_events(planes)
    win = [(a, b) for a, b, n in host if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = win[0]
    devices = [p for p in planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    ops: dict[str, list[float]] = {}
    programs: dict[str, list[float]] = {}
    busy_total = 0.0
    gaps: list[tuple[float, float]] = []
    labels: dict[str, tuple[str, str]] = {}
    for p in devices:
        lines = {line.name: list(line.events) for line in p.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       _program(e.name)) for e in lines.get(MODULES_LINE, [])
                      if e.start_ns + e.duration_ns > w0 and e.start_ns < w1)
        starts = [m[0] for m in mods]
        for a, b, name in mods:
            acc = programs.setdefault(name, [0.0, 0])
            acc[0] += (min(b, w1) - max(a, w0)) * 1e-9
            acc[1] += 1
        intervals = []
        for e in lines.get(OPS_LINE, []):
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if b <= w0 or a >= w1:
                continue
            intervals.append((a, b))
            k = bisect.bisect_right(starts, a) - 1
            prog = mods[k][2] if k >= 0 and a < mods[k][1] else "?"
            if e.name not in labels:
                labels[e.name] = op_label(e.name)
            label, opcode = labels[e.name]
            if opcode in WRAPPERS:
                continue
            acc = ops.setdefault(f"{prog}: {label}", [0.0, 0])
            acc[0] += (min(b, w1) - max(a, w0)) * 1e-9
            acc[1] += 1
        busy = clip(union(intervals), w0, w1)
        busy_total += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * 1e-9 / len(devices),
        "devices": len(devices),
        "ops": ops,
        "programs": programs,
        "device_ops": [[n, v[0]] for n, v in top_ops],
        "idle_gaps": [[_label(host, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in longest],
    }


def reduce_dir(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(trace_dir)).planes)


def time_of(table: dict, pattern: str) -> tuple[float, int]:
    """Total seconds and count of the entries whose name matches."""
    rx = re.compile(pattern)
    hits = [v for k, v in table.items() if rx.search(k)]
    return sum(v[0] for v in hits), sum(int(v[1]) for v in hits)
