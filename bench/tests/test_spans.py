"""The reduction of the engine's own spans and device scopes
(``bench/spans.py``): on a made-up trace with the shapes of the chip's,
and on a real trace of a tiny paged engine on the CPU."""

import collections
import dataclasses
import struct

import jax
import pytest

from bench import spans as SPANS
from bench import trace_reduce as TR

Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Ev = collections.namedtuple("Ev", "name start_ns duration_ns stats",
                            defaults=((),))

STEP = "jit(serve_step)/while/body"
LOOP = "%while.13 = (s32[]) while((s32[]) %tuple.58), condition=%region_9"
SAMPLE = "%fusion.7 = s32[8]{0} fusion(bf16[8,50304]{1,0} %p), kind=kLoop"
WRITE = "%fusion.9 = bf16[560,16,32,80]{3,2,1,0} fusion(%a, %b), kind=kLoop"
CAST = "%convert_element_type.204 = f32[560,16,32,80]{3,2,1,0} convert(%x)"
MATMUL = "%fusion.131 = bf16[8,6912]{1,0} fusion(%y, %w), kind=kOutput"
COPY = "%copy.59 = bf16[1,1,560,16,32,80]{5,4,3,2,1,0} copy(%z)"
SCOPES = {SAMPLE: "engine.sample", WRITE: "engine.kv_write",
          CAST: "engine.pool_cast"}


def _host(*events):
    return Plane("/host:CPU", [Line("python3", list(events))])


def _device():
    return Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_serve_step(340254)", 400, 5600)]),
        Line("XLA Ops", [
            Ev(LOOP, 1000, 4000),
            Ev(SAMPLE, 1500, 1000),
            Ev(WRITE, 500, 1000),   # before the window: [1000, 1500] counts
            Ev(CAST, 2500, 500),
            Ev(MATMUL, 3000, 500),
            Ev(COPY, 3500, 700),
        ]),
    ])


def _tick():
    """One tick with every phase; the waits take 100 + 300 + 2000 ns."""
    return [
        Ev("engine.tick", 2000, 5000),
        Ev("engine.admit", 2000, 1000),
        Ev("engine.admit.request", 2100, 500, (("rid", 7),)),
        Ev("engine.admit.wait", 2200, 100),
        Ev("engine.prefill", 3000, 1000),
        Ev("engine.prefill.chunk", 3000, 600, (("rid", 7),)),
        Ev("engine.prefill.wait", 3600, 300),
        Ev("engine.decode.dispatch", 4000, 500),
        Ev("np.asarray(jax.Array)", 4600, 1000),   # not an engine span
        Ev("engine.decode.wait", 4500, 2000),
        Ev("engine.emit", 6500, 400),
    ]


def _trace():
    host = _host(Ev("bench.window", 1000, 10000),
                 Ev("bench.tick", 1900, 5200),
                 Ev("engine.tick", 500, 400),        # before the window
                 Ev("engine.tick", 10500, 1000),     # runs past its end
                 *_tick())
    return [host, _device()]


# -- protobuf, written by hand: the fields of xplane.proto that are read --


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    out = b""
    for num, value in fields:
        if isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        elif isinstance(value, float):           # a double: fixed 64 bits
            out += _varint(num << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _entry(key: int, value: bytes) -> bytes:
    return _msg((1, key), (2, value))


def _xspace() -> bytes:
    tf_op, flops, source, interned = 3, 4, 5, 9
    stat_names = [_entry(i, _msg((1, i), (2, name))) for i, name in
                  ((tf_op, "tf_op"), (flops, "flops"), (source, "source"),
                   (interned, f"{STEP}/engine.pool_cast/convert"))]
    ops = [
        # the scope nearest the op wins
        _msg((1, 1), (2, SAMPLE), (5, _msg((1, tf_op), (
            5, f"{STEP}/engine.kv_write/engine.sample/argmax:")))),
        # an interned string: the value names a stat metadata
        _msg((1, 2), (2, CAST), (4, "convert_element_type.204"),
             (5, _msg((1, tf_op), (7, interned)))),
        # only the op_name counts: not the source file engine.py
        _msg((1, 3), (2, MATMUL), (5, _msg((1, flops), (2, 1.5e9))),
             (5, _msg((1, source), (5, "repro/serving/engine.py:1790"))),
             (5, _msg((1, tf_op), (5, f"{STEP}/dot_general:")))),
        _msg((1, 4), (2, COPY)),                  # metadata with no stats
    ]
    device = _msg((1, 5), (2, "/device:TPU:0"),
                  (3, _msg((2, "XLA Ops"), (4, _msg((1, 1), (2, 7))))),
                  *[(5, e) for e in stat_names],
                  *[(4, _entry(i + 1, op)) for i, op in enumerate(ops)])
    host = _msg((2, "/host:CPU"), (5, stat_names[0]), (4, _entry(1, _msg(
        (2, WRITE), (5, _msg((1, tf_op), (5, f"{STEP}/engine.sample")))))))
    return _msg((1, host), (1, device), (3, "a warning"))


def test_op_scopes_read_the_device_plane_metadata():
    assert SPANS.op_scopes(_xspace()) == {
        SAMPLE: "engine.sample",
        CAST: "engine.pool_cast",
        "convert_element_type.204": "engine.pool_cast",
    }


def test_the_innermost_engine_scope_of_an_op_name():
    assert SPANS.scope_of(f"{STEP}/engine.kv_write/scatter") == (
        "engine.kv_write")
    assert SPANS.scope_of(f"{STEP}/engine.sample/engine.kv_write/x") == (
        "engine.kv_write")
    # an operand's name in HLO text is not a scope
    assert SPANS.scope_of("fusion(s8[256,80] %engine.kv_write.18)") == (
        SPANS.NO_SCOPE)
    assert SPANS.scope_of("") == SPANS.NO_SCOPE


def test_scopes_are_clipped_to_the_window_and_sum_to_the_op_time():
    r = SPANS.reduce(_trace(), SCOPES)
    scopes = r["scopes"]["jit_serve_step"]
    assert scopes == {
        "engine.sample": [pytest.approx(1000e-9), 1],
        "engine.kv_write": [pytest.approx(500e-9), 1],
        "engine.pool_cast": [pytest.approx(500e-9), 1],
        SPANS.NO_SCOPE: [pytest.approx(1200e-9), 2],
    }
    # the loop wraps the others and is left out, as the op table does
    ops = TR.reduce(_trace())["ops"]
    assert sum(v[0] for v in scopes.values()) == pytest.approx(
        TR.time_of(ops, r"^jit_serve_step: ")[0])


def test_self_time_is_the_span_less_its_engine_children():
    spans = SPANS.reduce(_trace())["spans"]
    ns = lambda name: (spans[name]["n"], round(spans[name]["total_s"] * 1e9),
                       round(spans[name]["self_s"] * 1e9))
    # the two ticks that leave the window are not counted
    assert ns("engine.tick") == (1, 5000, 100)
    assert ns("engine.admit") == (1, 1000, 500)
    assert ns("engine.admit.request") == (1, 500, 400)
    assert ns("engine.prefill") == (1, 1000, 100)
    # host events of JAX inside a span are not engine children
    assert ns("engine.decode.wait") == (1, 2000, 2000)
    assert "np.asarray(jax.Array)" not in spans


def test_tick_host_time_leaves_out_its_waits():
    ticks = SPANS.reduce(_trace())["ticks"]
    assert ticks == [pytest.approx((5000 - 100 - 300 - 2000) * 1e-9)]


def test_idle_device_time_goes_to_the_innermost_engine_span():
    host = _host(Ev("engine.tick", 50, 3950),
                 Ev("engine.decode.dispatch", 60, 30),
                 Ev("engine.decode.wait", 100, 2900),
                 Ev("np.asarray(jax.Array)", 120, 2880),
                 Ev("engine.emit", 3100, 100))
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev(MATMUL, 0, 100), Ev(COPY, 3000, 100)])])
    idle = SPANS.reduce([host, dev])["idle"]
    assert idle == {"engine.decode.wait": [pytest.approx(2900e-9), 1],
                    "engine.tick": [pytest.approx(900e-9), 1]}
    # in the window of the made-up trace the chip idles after the tick
    assert SPANS.reduce(_trace())["idle"] == {
        SPANS.NO_SPAN: [pytest.approx(6000e-9), 1]}


def test_a_trace_without_the_window_span_is_read_whole():
    r = SPANS.reduce([_host(*_tick())])
    assert r["window_s"] == pytest.approx(5000e-9)
    assert r["spans"]["engine.tick"]["n"] == 1
    assert r["scopes"] == {}
    with pytest.raises(ValueError, match="no events"):
        SPANS.reduce([_host()])


def test_tables_print_every_part():
    text = "\n".join(SPANS.tables(SPANS.reduce(_trace(), SCOPES)))
    for part in ("scopes of jit_serve_step", "engine.pool_cast",
                 SPANS.NO_SCOPE, "engine.decode.wait", "ticks: n=1",
                 SPANS.NO_SPAN):
        assert part in text


# -- a real trace of the engine on the CPU ----------------------------------


PHASES = ("engine.admit", "engine.prefill", "engine.decode.dispatch",
          "engine.decode.wait", "engine.emit")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny paged engine (the smoke config, int8 pool, WTA sampler) that
    serves three requests in two slots, the last ticks under the
    profiler."""
    from repro.configs import get_smoke_config
    from repro.models import get_model_fns
    from repro.serving import ServeConfig, ServingEngine

    cfg = dataclasses.replace(get_smoke_config("stablelm-3b"),
                              kv_cache_dtype="int8", wta_head=True)
    params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, ServeConfig(
        max_batch=2, max_new_tokens=4, max_len=64, kv_block_size=8,
        prefill_chunk=8))

    def serve():
        for p in ([*range(1, 12)], [3, 4, 5], [7, 7, 7, 7]):
            eng.submit(p, 4)
        while eng.sched.has_work():
            eng.tick()

    serve()   # compiles
    m0 = eng.metrics()
    path = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(path)):
        serve()
    return path, m0, eng.metrics()


def _engine_events(path):
    from jax.profiler import ProfileData

    planes = ProfileData.from_file(TR.find_xplane(str(path))).planes
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
            for p in planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith(SPANS.PREFIX)]


def test_a_tick_holds_every_phase_and_request_spans_carry_the_rid(traced):
    path, _, _ = traced
    events = _engine_events(path)
    ticks = [(a, b) for a, b, name, _ in events if name == SPANS.TICK]
    inside = lambda a, b: any(t0 <= a and b <= t1 for t0, t1 in ticks)
    for phase in PHASES:
        found = [(a, b) for a, b, name, _ in events if name == phase]
        assert found and all(inside(a, b) for a, b in found), phase
    for name in ("engine.admit.request", "engine.prefill.chunk"):
        args = [stats for _, _, n, stats in events if n == name]
        assert args and all("rid" in s for s in args), name
    rids = {s["rid"] for _, _, n, s in events if n == "engine.admit.request"}
    assert len(rids) == 3


def test_the_engine_trace_gives_tick_time_and_the_counters_queue_wait(
        traced):
    path, m0, m1 = traced
    r = SPANS.reduce_dir(str(path))
    assert r["ticks"] and all(t > 0 for t in r["ticks"])
    total = r["spans"][SPANS.TICK]["total_s"]
    assert sum(r["ticks"]) < total
    assert m1.admissions - m0.admissions == 3
    # the third request waits for a slot
    assert m1.queue_wait_s - m0.queue_wait_s > 0
