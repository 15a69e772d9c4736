"""From a profiler trace to the serving engine's own spans and scopes.

The engine names its host phases with ``jax.profiler.TraceAnnotation``
spans: ``engine.tick`` and, inside it, ``engine.admit`` (with
``engine.admit.request`` for each request seated, argument ``rid``),
``engine.preempt``, ``engine.prefill`` (with ``engine.prefill.chunk``
for each chunk, argument ``rid``), ``engine.cow``,
``engine.decode.dispatch``, ``engine.emit`` or ``engine.spec``.  A span
whose name ends in ``.wait`` is the host blocked on a device result
(``engine.admit.wait``, ``engine.prefill.wait``, ``engine.decode.wait``).
The step programs name their work with ``jax.named_scope``
(``engine.sample``, ``engine.kv_write``, ``engine.pool_cast``), which
XLA keeps in each op's ``op_name``.  The profiler writes it as the
``tf_op`` stat of the op's event metadata on the device plane, which
``ProfileData`` does not show: ``op_scopes`` reads it from the
``.xplane.pb`` itself.

``reduce`` gives, over the traced window:

- ``scopes``: ``{program: {scope: [device seconds, ops]}}``, each op
  under the innermost ``engine.*`` scope of its ``op_name``, or
  ``(no scope)``.  Ops are clipped to the window and control flow is
  left out as ``trace_reduce`` does, so a program's scopes sum to its
  op time in ``trace_reduce``'s ``ops`` table.
- ``spans``: ``{name: {"n", "total_s", "self_s"}}`` over the engine
  spans wholly inside the window; self time is the span less the union
  of the engine spans nested in it on its thread.
- ``ticks``: the host seconds of each ``engine.tick`` wholly inside the
  window: its duration less the union of the ``engine.*.wait`` spans in
  it.
- ``idle``: ``{span: [seconds, gaps]}``, the device's idle gaps in the
  window (as ``trace_reduce`` finds them) by the innermost engine span
  at each gap's middle, or ``(no span)``: what the host was doing while
  the chip waited.

The window is the harness's ``bench.window`` span where the trace has
one, else the whole trace.  As a script it prints the tables of
the newest ``.xplane.pb`` under a directory, such as one that
``jax.profiler.trace`` wrote around some ``ServingEngine.tick`` calls:

    python3 bench/spans.py <trace dir>
"""

from __future__ import annotations

import bisect
import os
import re
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce as TR  # noqa: E402

PREFIX = "engine."
NO_SCOPE = "(no scope)"
NO_SPAN = "(no span)"
OP_NAME_STAT = "tf_op"
TICK = "engine.tick"
WAIT_SUFFIX = ".wait"
# an engine scope as a component of an op_name path
_SCOPE = re.compile(r"(?:^|/)(engine\.[A-Za-z0-9_.]+)")


def scope_of(op_name: str) -> str:
    """The innermost ``engine.*`` scope of an ``op_name``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else NO_SCOPE


# -- the op metadata of an XSpace (tsl/profiler/protobuf/xplane.proto):
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (map entries: key = 1, value = 2);
# XEventMetadata.name = 2, .display_name = 4, .stats = 5;
# XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
# .ref_value = 7 (the id of a stat metadata whose name is the string).


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map_entry(buf) -> tuple[int, object]:
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def op_scopes(xspace: bytes) -> dict[str, str]:
    """The innermost ``engine.*`` scope in the ``op_name`` (stat
    ``tf_op``) of each device op that has one, keyed by the op's event
    name and display name, from a serialized XSpace."""
    out: dict[str, str] = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((_text(v) for n, v in fields if n == 2), "")
        if not TR.DEVICE_PLANE.match(name):
            continue
        strings = {}
        for n, v in fields:
            if n == 5:
                key, meta = _map_entry(v)
                strings[key] = next(
                    (_text(x) for k, x in _fields(meta) if k == 2), "")
        op_name_ids = {k for k, v in strings.items() if v == OP_NAME_STAT}
        for n, v in fields:
            if n != 4:
                continue
            names, scope = [], NO_SCOPE
            for k, x in _fields(_map_entry(v)[1]):
                if k in (2, 4):
                    names.append(_text(x))
                elif k == 5:
                    stat = dict(_fields(x))
                    if stat.get(1, 0) in op_name_ids:
                        scope = scope_of(
                            _text(stat[5]) if 5 in stat
                            else strings.get(stat.get(7), ""))
            if scope != NO_SCOPE:
                out.update((n_, scope) for n_ in names if n_)
    return out


def _window(planes) -> tuple[float, float]:
    spans = [(e.start_ns, e.start_ns + e.duration_ns)
             for p in planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name == TR.WINDOW_SPAN]
    if spans:
        return spans[0]
    ends = [(e.start_ns, e.start_ns + e.duration_ns)
            for p in planes for line in p.lines for e in line.events]
    if not ends:
        raise ValueError("the trace holds no events")
    return min(a for a, _ in ends), max(b for _, b in ends)


def _scopes(planes, scopes: dict, w0: float, w1: float) -> dict:
    out: dict[str, dict[str, list]] = {}
    known: dict[tuple[str, str], tuple[str, str]] = {}
    for p in planes:
        if not TR.DEVICE_PLANE.match(p.name):
            continue
        lines = {line.name: list(line.events) for line in p.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       TR._program(e.name))
                      for e in lines.get(TR.MODULES_LINE, [])
                      if e.start_ns + e.duration_ns > w0 and e.start_ns < w1)
        starts = [m[0] for m in mods]
        for e in lines.get(TR.OPS_LINE, []):
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if b <= w0 or a >= w1:
                continue
            k = bisect.bisect_right(starts, a) - 1
            prog = mods[k][2] if k >= 0 and a < mods[k][1] else "?"
            if (prog, e.name) not in known:
                known[prog, e.name] = (TR.op_label(e.name)[1],
                                       scopes.get(e.name, NO_SCOPE))
            opcode, scope = known[prog, e.name]
            if opcode in TR.WRAPPERS:
                continue
            acc = out.setdefault(prog, {}).setdefault(scope, [0.0, 0])
            acc[0] += (min(b, w1) - max(a, w0)) * 1e-9
            acc[1] += 1
    return out


def _forest(events) -> list:
    """The spans of one thread as nodes ``[start, end, name, children]``,
    each child wholly inside its parent; returns every node."""
    nodes, stack = [], []
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        node = [a, b, name, []]
        if stack and b <= stack[-1][1]:
            stack[-1][3].append(node)
        nodes.append(node)
        stack.append(node)
    return nodes


def _covered(intervals) -> float:
    return sum(b - a for a, b in TR.union(intervals))


def _waits(node) -> list[tuple[float, float]]:
    out = []
    for c in node[3]:
        if c[2].endswith(WAIT_SUFFIX):
            out.append((c[0], c[1]))
        out += _waits(c)
    return out


def _spans_and_ticks(planes, w0: float, w1: float):
    spans: dict[str, dict] = {}
    ticks: list[float] = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in line.events if e.name.startswith(PREFIX)]
            for node in _forest(events):
                a, b, name, kids = node
                if a < w0 or b > w1:
                    continue
                acc = spans.setdefault(
                    name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
                acc["n"] += 1
                acc["total_s"] += (b - a) * 1e-9
                acc["self_s"] += (b - a - _covered(
                    [(c[0], c[1]) for c in kids])) * 1e-9
                if name == TICK:
                    ticks.append((b - a - _covered(_waits(node))) * 1e-9)
    return spans, ticks


def _engine_spans(planes) -> list[tuple[float, float, str]]:
    return sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for p in planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith(PREFIX)),
                  key=lambda s: (s[0], -s[1]))


def _idle(planes, w0: float, w1: float) -> dict:
    """Idle device time by the innermost engine span at each gap's middle
    (the spans of one thread nest, so a stack of the open spans finds
    it in one pass)."""
    spans = _engine_spans(planes)
    out: dict[str, list] = {}
    for p in planes:
        if not TR.DEVICE_PLANE.match(p.name):
            continue
        ops = [(e.start_ns, e.start_ns + e.duration_ns)
               for line in p.lines if line.name == TR.OPS_LINE
               for e in line.events]
        busy = TR.clip(TR.union(ops), w0, w1)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        stack, j = [], 0
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            t = (a + b) / 2
            while j < len(spans) and spans[j][0] <= t:
                stack.append(spans[j])
                j += 1
            stack = [s for s in stack if s[1] >= t]
            name = stack[-1][2] if stack else NO_SPAN
            acc = out.setdefault(name, [0.0, 0])
            acc[0] += (b - a) * 1e-9
            acc[1] += 1
    return out


def reduce(planes, scopes: dict | None = None) -> dict:
    """Device time by engine scope, engine span times and the host time
    of each tick, over the traced window; times in seconds.

    ``planes`` is ``ProfileData.planes`` (or objects with the same
    ``name`` / ``lines`` / ``events`` shape); ``scopes`` maps a device
    op's event name to its engine scope (``op_scopes``)."""
    planes = list(planes)
    w0, w1 = _window(planes)
    spans, ticks = _spans_and_ticks(planes, w0, w1)
    return {"window_s": (w1 - w0) * 1e-9,
            "scopes": _scopes(planes, scopes or {}, w0, w1),
            "spans": spans, "ticks": ticks, "idle": _idle(planes, w0, w1)}


def reduce_dir(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    with open(TR.find_xplane(trace_dir), "rb") as f:
        xspace = f.read()
    return reduce(ProfileData.from_serialized_xspace(xspace).planes,
                  op_scopes(xspace))


def scope_time(r: dict, program: str, scope: str) -> float:
    """Device seconds under ``scope`` in the programs whose name matches
    the pattern ``program``, in a reduction."""
    rx = re.compile(program)
    return sum(scopes.get(scope, (0.0, 0))[0]
               for prog, scopes in r["scopes"].items() if rx.search(prog))


def tables(r: dict) -> list[str]:
    """The tables of a reduction, as lines of text."""
    out = [f"window_s={r['window_s']:.6f}"]
    for prog, scopes in sorted(r["scopes"].items()):
        total = sum(v[0] for v in scopes.values())
        out.append(f"scopes of {prog}: {total * 1e3:.3f} ms of ops")
        for scope, (secs, n) in sorted(scopes.items(), key=lambda kv:
                                       -kv[1][0]):
            share = 100 * secs / total if total else 0.0
            out.append(f"  {scope:<24} {secs * 1e3:12.3f} ms "
                       f"{share:6.2f}% {n:9d} ops")
    out.append("spans: name n total_ms self_ms mean_ms")
    for name, s in sorted(r["spans"].items()):
        out.append(f"  {name:<24} {s['n']:7d} {s['total_s'] * 1e3:12.3f} "
                   f"{s['self_s'] * 1e3:12.3f} "
                   f"{s['total_s'] * 1e3 / s['n']:10.3f}")
    ticks = sorted(r["ticks"])
    if ticks:
        out.append(f"ticks: n={len(ticks)} host_ms mean="
                   f"{1e3 * sum(ticks) / len(ticks):.3f} median="
                   f"{1e3 * ticks[len(ticks) // 2]:.3f} "
                   f"max={1e3 * ticks[-1]:.3f}")
    if r["idle"]:
        out.append("idle device time by engine span: name ms gaps")
        for name, (secs, n) in sorted(r["idle"].items(),
                                      key=lambda kv: -kv[1][0]):
            out.append(f"  {name:<24} {secs * 1e3:12.3f} {n:9d}")
    return out


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.strip().splitlines()[-1].strip())
    print("\n".join(tables(reduce_dir(sys.argv[1]))))
