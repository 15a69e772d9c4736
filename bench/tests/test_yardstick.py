"""The benchmark's yardstick on the CPU: statistics, operation and byte
counts, the table of peaks, the traffic generator, the trace reduction
and the loader that finds a cell's parts by name."""

import collections
import json
import math
import os
import shutil

import numpy as np
import pytest

from bench import roofline as RL
from bench import spec as SPEC
from bench import stats as ST
from bench import trace_reduce as TR
from bench import traffic as TRAFFIC

CONFIGS = {c: SPEC.load_json(SPEC.config_file(c)) for c in
           ("stablelm-3b", "stablelm-3b-raca", "deepseek-coder-33b-pp8")}


# -- statistics --------------------------------------------------------------


def test_percentile_interpolates_between_order_statistics():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert ST.percentile(xs, 0) == 1.0
    assert ST.percentile(xs, 100) == 4.0
    assert ST.percentile(xs, 50) == pytest.approx(2.5)
    assert ST.percentile(xs, 90) == pytest.approx(3.7)


def test_percentile_keeps_requests_that_never_answered():
    answered = [0.1] * 9
    assert ST.percentile(answered + [math.inf], 50) == pytest.approx(0.1)
    assert ST.percentile(answered + [math.inf], 100) == math.inf
    # two of ten missing: the 90th percentile lands among them
    assert ST.percentile([0.1] * 8 + [math.inf] * 2, 90) == math.inf


def test_rate_and_gaps_inside_the_window():
    assert ST.rate(300, 30.0) == 10.0
    with pytest.raises(ValueError):
        ST.rate(1, 0.0)
    times = [0.5, 1.0, 1.5, 2.5, 4.0]
    assert ST.gaps_within(times, 0.9, 3.0) == [0.5, 1.0]


def test_spread_is_the_interquartile_distance_over_the_median():
    assert ST.spread([1.0] * 6) == 0.0
    vals = [9.0, 10.0, 10.0, 10.0, 10.0, 11.0]
    q1, q2, q3 = __import__("statistics").quantiles(vals, n=4)
    assert ST.spread(vals) == pytest.approx((q3 - q1) / q2)


# -- operations and bytes, counted by hand -----------------------------------


def test_decode_attention_counts_stablelm():
    m = CONFIGS["stablelm-3b"]["model"]
    flops, byts = RL.decode_attention(m, "same", [1000, 500])
    # per layer: 4 * 32 heads * 80 * 1500 positions; 32 layers
    assert flops == 32 * 4 * 32 * 80 * 1500
    # K and V of 1500 positions in bf16, plus a bf16 query and readout
    # of 32 x 80 for each of the 2 requests
    assert byts == 32 * (1500 * 2 * 32 * 80 * 2 + 2 * 2 * 32 * 80 * 2)


def test_decode_attention_counts_int8_pool():
    m = CONFIGS["stablelm-3b-raca"]["model"]
    _, byts = RL.decode_attention(m, "int8", [100])
    # int8 codes and a float32 scale per (position, head), K and V
    assert byts == 32 * (100 * 2 * 32 * (80 + 4) + 2 * 32 * 80 * 2)


def test_prefill_attention_counts_deepseek():
    m = CONFIGS["deepseek-coder-33b-pp8"]["model"]
    flops, byts = RL.prefill_attention(m, "same", q0=256, c=128)
    pairs = 128 * 256 + 128 * 129 // 2          # causal (query, key) pairs
    assert flops == 8 * 4 * 56 * 128 * pairs
    kv = 384 * 2 * 8 * 128 * 2                   # K/V of positions 0..383
    assert byts == 8 * (kv + 128 * 2 * 56 * 128 * 2)


def test_token_flops_stablelm():
    m = CONFIGS["stablelm-3b"]["model"]
    per_layer = 2560 * (2 * 2560 + 2 * 2560) + 3 * 2560 * 6912
    assert RL.matmul_params(m) == 32 * per_layer
    f = RL.token_flops(m, position=99, head=True)
    assert f == 2 * 32 * per_layer + 4 * 32 * 32 * 80 * 100 + 2 * 2560 * 50304
    c = RL.chunk_flops(m, q0=0, c=1)
    assert c == RL.token_flops(m, position=0, head=True)


def test_roofline_share_takes_the_binding_bound():
    peak = RL.peaks("TPU v5 lite")
    # 819 MB in 2 ms: half the bandwidth bound
    assert RL.roofline_share(1.0, 819e6, 2e-3, peak) == pytest.approx(50.0)
    # 197 GFLOP in 1 s: a tenth of a percent of the compute bound
    assert RL.roofline_share(197e9, 0.0, 1.0, peak) == pytest.approx(0.1)
    assert RL.roofline_share(0.0, 0.0, 1.0, peak) is None


def test_peaks_refuse_an_unknown_device_kind():
    assert RL.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        RL.peaks("TPU v4")
    with pytest.raises(KeyError):
        RL.peaks("cpu")


# -- traffic -----------------------------------------------------------------


def test_every_seed_sends_the_same_sizes_in_another_order():
    mix = SPEC.load_json(SPEC.traffic_file("long_decode"))
    a = TRAFFIC.generate(mix, 1000, seed=1, seconds=30)
    b = TRAFFIC.generate(mix, 1000, seed=2 ** 33 + 5, seconds=30)
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in b)
    assert sorted(i.out_len for i in a) == sorted(i.out_len for i in b)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in b]
    lo, hi = TRAFFIC.prompt_range(mix)
    assert all(lo <= len(i.prompt) <= hi and 0 < min(i.prompt) for i in a)
    per_client = collections.Counter(i.client for i in a)
    assert set(per_client.values()) == {mix["requests_per_client"]}
    # round r is the r-th request of every client
    c = mix["clients"]
    assert [i.client for i in a[:2 * c]] == list(range(c)) * 2


def test_every_round_holds_one_length_of_every_stratum():
    d = {"median": 512, "sigma": 0.5, "min": 128, "max": 1024}
    strata = TRAFFIC.lognormal_quantiles(d, 8 * 24).reshape(8, 24)
    for seed in (1, 2 ** 40):
        x = TRAFFIC.by_rounds(d, 8, 24, np.random.default_rng(seed))
        assert sorted(x.ravel()) == sorted(strata.ravel())
        for row in x:
            assert all(lo <= v <= hi for v, lo, hi in
                       zip(sorted(row), strata[:, 0], strata[:, -1]))


def test_open_loop_arrivals_and_shared_prefixes():
    mix = SPEC.load_json(SPEC.traffic_file("prefix_chat"))
    items = TRAFFIC.generate(mix, 32256, seed=7, seconds=30)
    lead, rate = mix["lead_in_s"], mix["rate_per_s"]
    due = [i.due for i in items]
    assert due == sorted(due)
    in_window = [d for d in due if lead <= d < lead + 30]
    assert len(in_window) == round(rate * 30)
    assert len([d for d in due if d < lead]) == round(rate * lead)
    other = TRAFFIC.generate(mix, 32256, seed=8, seconds=30)
    assert len(other) == len(items) and [i.due for i in other] != due
    counts = collections.Counter(i.prefix for i in items)
    assert counts[0] > counts[1] > counts[2] > counts[3] > 0   # Zipf
    heads = {i.prefix: tuple(i.prompt[:1024]) for i in items}
    assert len(set(heads.values())) == 4
    lo, hi = TRAFFIC.prompt_range(mix)
    assert lo == 1024 + 32 and hi == 1024 + 1024
    assert all(lo <= len(i.prompt) <= hi for i in items)


def test_stratified_lengths_follow_the_lognormal():
    d = {"median": 256, "sigma": 0.4, "min": 1, "max": 10 ** 6}
    x = TRAFFIC.lognormal_lengths(d, 1001, np.random.default_rng(0))
    assert np.median(x) == 256
    assert np.std(np.log(x)) == pytest.approx(0.4, rel=0.02)


# -- the trace reduction -----------------------------------------------------

Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Ev = collections.namedtuple("Ev", "name start_ns duration_ns")


# op names as the chip's trace gives them (HLO text, cut short), from a
# traced run of deepseek-coder-33b-pp8.prefix_chat; times are made up
DECODE_KERNEL = ('%closed_call.13 = f32[16,56,128]{2,1,0:T(8,128)S(1)} '
                 'custom-call(s32[16,144]{1,0:T(8,128)S(1)} %copy-done.2), '
                 'custom_call_target="tpu_custom_call"')
MLP = ('%fusion.131 = bf16[16,19200]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8,'
       '7168,19200]{2,1,0:T(8,128)(2,1)} %get-tuple-element.677), '
       'kind=kOutput')
LOOP = ('%while.13 = (s32[]{:T(128)}, bf16[16,1,7168]{2,0,1:T(8,128)(2,1)}) '
        'while((s32[]{:T(128)}) %tuple.58), condition=%region_9')
CHUNK_OP = ('%fusion.108 = bf16[128,19200]{1,0:T(8,128)(2,1)S(1)} fusion('
            'bf16[8,7168,19200]{2,1,0:T(8,128)(2,1)} %get-tuple-element.548)')


def _trace():
    host = Plane("/host:CPU", [Line("python3", [
        Ev("bench.window", 1000, 10000),
        Ev("bench.tick", 1000, 5000),
        Ev("np.asarray(jax.Array)", 3000, 2500),
        Ev("bench.wait", 6000, 5000),
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_serve_step(3402542433548617038)",
                                1000, 2500),
                             Ev("jit_suffix_chunk(1556650232)", 7000, 1000)]),
        Line("XLA Ops", [Ev(LOOP, 1000, 2000),
                         Ev(MLP, 1000, 1000),
                         Ev(DECODE_KERNEL, 1500, 1500),
                         Ev(CHUNK_OP, 7000, 1000),
                         Ev(MLP, 500, 300)]),      # before the window
        Line("Async XLA Ops", [Ev("%copy-start = (bf16[2]) copy-start()",
                                  4000, 500)]),
    ])
    other = Plane("/device:TPU_NON_CORE:0", [Line("XLA Ops", [Ev("x", 0, 99999)])])
    return [host, dev, other]


def test_reduction_busy_union_idle_gaps_and_times():
    r = TR.reduce(_trace())
    assert r["window_s"] == pytest.approx(10000e-9)
    # ops overlap on [1000, 3000]: the union is [1000, 3000] + [7000, 8000];
    # the async copy at 4000 is not an op run
    assert r["busy_s"] == pytest.approx(3000e-9)
    assert r["devices"] == 1
    assert r["programs"]["jit_serve_step"] == [pytest.approx(2500e-9), 1]
    assert TR.time_of(r["programs"], r"suffix_chunk") == (
        pytest.approx(1000e-9), 1)
    kernel = (r"serve_step: \S+ f32\[\d+,56,128\] "
              r"custom-call\(tpu_custom_call\)")
    assert TR.time_of(r["ops"], kernel) == (pytest.approx(1500e-9), 1)
    assert TR.time_of(r["ops"], r"suffix_chunk: %fusion.108") == (
        pytest.approx(1000e-9), 1)
    # the op before the window is clipped away; the while loop wraps ops
    assert r["ops"]["jit_serve_step: %fusion.131 bf16[16,19200] fusion"] == [
        pytest.approx(1000e-9), 1]
    assert not any("while" in name for name, _ in r["device_ops"])
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == [pytest.approx(4000e-9),
                                   pytest.approx(3000e-9)]
    assert gaps[0][0] == "bench.tick / np.asarray(jax.Array)"
    assert gaps[1][0] == "bench.wait"
    assert r["device_ops"][0][0].startswith("jit_serve_step: %closed_call.13")


def test_op_labels_keep_the_result_and_the_kernel_target():
    assert TR.op_label(DECODE_KERNEL) == (
        "%closed_call.13 f32[16,56,128] custom-call(tpu_custom_call)",
        "custom-call(tpu_custom_call)")
    assert TR.op_label(LOOP) == ("%while.13 (tuple) while", "while")
    assert TR.op_label(MLP)[0] == "%fusion.131 bf16[16,19200] fusion"


def test_reduction_needs_the_window_span_and_a_device():
    host, dev, _ = _trace()
    with pytest.raises(ValueError, match="bench.window"):
        TR.reduce([Plane("/host:CPU", [Line("main", [])]), dev])
    with pytest.raises(ValueError, match="device plane"):
        TR.reduce([host])


def test_union_and_clip():
    assert TR.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert TR.clip([(0, 2), (3, 9)], 1, 5) == [(1, 2), (3, 5)]


# -- the loader --------------------------------------------------------------


def test_every_cell_finds_its_parts_by_name():
    b = SPEC.benchmark()
    for w in b["workloads"]:
        cell = SPEC.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"] in ("closed_loop", "open_loop")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(SPEC.reader(m["name"]))
    for c in b["configs"]:
        assert c["file"] == os.path.relpath(SPEC.config_file(c["name"]),
                                            SPEC.ROOT)
    with pytest.raises(KeyError):
        SPEC.cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        SPEC.reader("no_such_metric")


def test_a_new_cell_and_metric_are_data_files_and_an_entry(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(SPEC.config_file("stablelm-3b"),
                bench / "configs" / "new-model.json")
    (bench / "traffic" / "new_mix.json").write_text(json.dumps(
        {"kind": "closed_loop", "clients": 2, "requests_per_client": 1,
         "prompt_len": {"median": 8, "sigma": 0, "min": 8, "max": 8},
         "output_len": {"median": 4, "sigma": 0, "min": 4, "max": 4}}))
    (bench / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "new-model.new_mix", "config": "new-model",
                       "traffic": "new_mix", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "new_metric",
                       "workloads": ["new-model.new_mix"]}],
    }))
    cell = SPEC.cell("new-model.new_mix", root=str(tmp_path),
                     bench_dir=str(bench))
    assert cell.config["registry"] == "stablelm-3b"
    assert cell.traffic["clients"] == 2
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert SPEC.reader("new_metric", bench_dir=str(bench))({"x": 21}) == 42


def test_benchmark_file_keeps_to_its_rules():
    import re

    b = SPEC.benchmark()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    metrics = b["end_to_end"] + b["per_layer"]
    cells = {w["name"] for w in b["workloads"]}
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads",
                                                              cells))
    for w in b["workloads"]:
        assert name.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
        assert os.path.exists(SPEC.traffic_file(w["traffic"]))
    for c in b["configs"]:
        assert os.path.exists(os.path.join(SPEC.ROOT, c["file"]))
        assert c["reduced"] == CONFIGS[c["name"]]["reduced"]


# -- the readers of per-layer metrics ----------------------------------------


def _ctx(config="deepseek-coder-33b-pp8"):
    from bench.harness import Work

    work = Work(decode_steps=[[2049, 2100], [2050, 2101]],
                chunks=[(0, 128), (1920, 128)])
    return {
        "model": CONFIGS[config]["model"], "kv_dtype": "same",
        "peak": RL.peaks("TPU v5 lite"), "trace": TR.reduce(_trace()),
        "work": work, "admitted_in_window": 4,
        "counters": {"decode_steps": 50, "occupancy_sum": 40.0,
                     "prefill_tokens": 8192, "prefill_tokens_saved": 0},
    }


def test_readers_compute_from_counters_trace_and_shapes():
    ctx = _ctx()
    read = lambda name: SPEC.reader(name)(ctx)
    assert read("batch_occupancy.decode") == pytest.approx(80.0)
    assert read("prefill_tok_per_req.chat") == pytest.approx(2048.0)
    assert read("decode_step_ms.decode") == pytest.approx(2500e-6)
    assert read("prefill_chunk_ms.chat") == pytest.approx(1000e-6)
    assert read("idle_share.decode") == pytest.approx(70.0)
    assert read("idle_share.chat") == pytest.approx(70.0)
    m = CONFIGS["deepseek-coder-33b-pp8"]["model"]
    flops, byts = 0.0, 0.0
    for step in ctx["work"].decode_steps:
        f, b = RL.decode_attention(m, "same", step)
        flops, byts = flops + f, byts + b
    want = RL.roofline_share(flops, byts, 1500e-9, ctx["peak"])
    assert read("paged_decode_roofline.decode") == pytest.approx(want)
    # the synthetic trace has no prefill kernel: nothing to read
    assert read("prefill_attn_roofline.chat") is None
    chunks = sum(RL.chunk_flops(m, q0, c) for q0, c in ctx["work"].chunks)
    assert read("prefill_mfu.chat") == pytest.approx(
        100 * chunks / (1000e-9 * 197e12))
    assert read("decode_mfu") > 0


def test_readers_return_nothing_without_a_trace():
    ctx = dict(_ctx(), trace=None, admitted_in_window=0)
    ctx["counters"] = dict(ctx["counters"], decode_steps=0)
    for m in SPEC.benchmark()["per_layer"]:
        assert SPEC.reader(m["name"])(ctx) is None, m["name"]
