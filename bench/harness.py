"""One run of one cell: set up, warm up, measure, check, report.

The system under test is the serving engine as ``launch/serve.py``
drives it: ``ServingEngine.submit`` and ``tick`` on the paged pool.  The
harness owns everything else: the weights (``bench/weights.py``, in the
layout of the cell's architecture), the traffic (``bench/traffic.py``),
the clock, the spans it traces around its calls into the engine
(``bench.tick``, ``bench.submit``, ``bench.wait``, ``bench.window``), the
reductions of the trace (``bench/trace_reduce.py``, and the engine's own
spans and scopes by ``bench/spans.py``), the metrics, and the comparison
with the architecture's reference that decides ``correct``.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import time

import jax
import numpy as np

from bench import reference as REF
from bench import roofline as RL
from bench import spans as SPANS
from bench import spec as SPEC
from bench import stats as ST
from bench import trace_reduce as TR
from bench import traffic as TRAFFIC
from bench import weights as WEIGHTS

CACHE_DIR = ".jax_cache"         # under the checkout, listed in .gitignore
TRACE_DIR = ".bench_trace"       # written and removed by a traced run
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Programs compiled, and loaded from the persistent cache, as JAX's
    monitoring events report them (a backend-compile event covers both;
    a cache hit marks the latter)."""

    def __init__(self):
        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_hits

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            self.requests += 1

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            self.cache_hits += 1


def peak_bytes() -> int:
    return max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    )


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is written, however fast it compiled."""
    path = os.path.join(root, CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _program():
    """The system under test, imported from the checkout's ``src``."""
    src = os.path.join(SPEC.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.configs import get_config
    from repro.launch.specs import params_specs
    from repro.serving import RequestState, ServeConfig, ServingEngine

    return get_config, params_specs, RequestState, ServeConfig, ServingEngine


def model_config(conf: dict, get_config, arch):
    """The program's ModelConfig for a configuration file, checked
    against the fields that its architecture ``arch`` reads from it (a
    JSON list among the overrides is a tuple, as ``layer_pattern`` is)."""
    mc = get_config(conf["registry"])
    overrides = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in conf.get("overrides", {}).items()}
    mc = dataclasses.replace(
        mc, **overrides,
        kv_cache_dtype=conf["kv_cache_dtype"],
        wta_head=conf["sampler"] == "wta",
    )
    want = arch.program_fields(conf["model"], conf["as_run"])
    have = {k: getattr(mc, k) for k in want}
    if have != want:
        bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
        raise ValueError(f"program config differs from the file: {bad}")
    return mc


def _check_layout(weights, mc, params_specs) -> None:
    want = jax.tree.map(lambda s: (s.shape, s.dtype), params_specs(mc))
    have = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
    if want != have:
        raise ValueError("the benchmark's weights do not fit the program's "
                         "parameter layout")


def bucket_of(buckets, n: int) -> int:
    return next(b for b in buckets if b >= n)


class Clock:
    """What the harness observed of each request: when it was due (open
    loop), submitted, and each token's time as ``tick`` returned."""

    def __init__(self):
        self.due: dict[int, float] = {}
        self.submit: dict[int, float] = {}
        self.tokens: dict[int, list[float]] = {}
        self.out: dict[int, list[int]] = {}
        self.late = 0.0

    def record(self, emitted, now: float) -> None:
        for rid, tok in emitted:
            self.tokens.setdefault(rid, []).append(now)
            self.out.setdefault(rid, []).append(int(tok))


@dataclasses.dataclass
class Work:
    """What the traced part of the window computed, for the counts of
    operations and bytes: the live length of every request in each decode
    step, and each prefill chunk's (start, length)."""

    decode_steps: list = dataclasses.field(default_factory=list)
    chunks: list = dataclasses.field(default_factory=list)
    pending: dict = dataclasses.field(default_factory=dict)


class Runner:
    def __init__(self, eng, clock, buckets, state_done, trace_dir, compiles):
        self.eng, self.clock, self.compiles = eng, clock, compiles
        self.buckets = buckets
        self.done_state = state_done
        self.trace_dir = trace_dir
        self.tracing = False
        self.trace_until = math.inf
        self.work = Work()
        self.prompt_len: dict[int, int] = {}
        self._prefill_seen = 0
        self._fifo: list[int] = []
        self.at_open: dict = {}

    def mark_open(self, t_open: float, trace_s: float) -> None:
        """The window opens: snapshot the engine's counters and the compile
        log, and start the trace of a traced run."""
        self.at_open = {
            "metrics": vars(self.eng.metrics()),
            "compiles": self.compiles.compiles,
            "loads": self.compiles.cache_hits,
        }
        # nothing should compile from here on: name whatever does
        jax.config.update("jax_log_compiles", True)
        if trace_s:
            self.start_trace(t_open + trace_s)

    # -- engine calls, each under a span of the harness --------------------

    def submit(self, prompt, out_len) -> int:
        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = self.eng.submit(prompt, max_new_tokens=out_len)
        now = time.perf_counter()
        self.clock.submit[rid] = now
        self.prompt_len[rid] = len(prompt)
        self._fifo.append(rid)
        return rid

    def tick(self) -> float:
        with jax.profiler.TraceAnnotation("bench.tick"):
            emitted = self.eng.tick()
        now = time.perf_counter()
        if self.tracing:
            self._note_work(emitted)
        self.clock.record(emitted, now)
        if self.tracing and now >= self.trace_until:
            self.stop_trace()
        return now

    def done(self, rid: int) -> bool:
        return self.eng.sched.request(rid).state is self.done_state

    # -- tracing ------------------------------------------------------------

    def start_trace(self, until: float) -> None:
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self._prefill_seen = self.eng.metrics().prefill_tokens
        self.tracing, self.trace_until = True, until

    def stop_trace(self) -> None:
        if not self.tracing:
            return
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False

    def _note_work(self, emitted) -> None:
        """Live lengths of this tick's decode step, and its prefill chunk
        (the engine computes at most one chunk a tick, for the oldest
        request still in prefill; a request's chunks end at its padded
        prompt's end, so each chunk's start is known once the request's
        first token comes)."""
        steps = []
        firsts = []
        seen: dict[int, int] = {}
        for rid, _ in emitted:
            i = len(self.clock.out.get(rid, [])) + seen.get(rid, 0)
            seen[rid] = seen.get(rid, 0) + 1
            b = bucket_of(self.buckets, self.prompt_len[rid])
            if i == 0:
                firsts.append(rid)
            else:
                steps.append(b + i)
        if steps:
            self.work.decode_steps.append(steps)
        with jax.profiler.TraceAnnotation("bench.metrics"):
            total = self.eng.metrics().prefill_tokens
        c, self._prefill_seen = total - self._prefill_seen, total
        while self._fifo and self._fifo[0] in self.clock.out:
            self._fifo.pop(0)
        if c and self._fifo:
            self.work.pending.setdefault(self._fifo[0], []).append(c)
        for rid in firsts:
            chunks = self.work.pending.pop(rid, [])
            end = bucket_of(self.buckets, self.prompt_len[rid])
            for c in reversed(chunks):
                self.work.chunks.append((end - c, c))
                end -= c


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------


def warm_up(run: Runner, mix: dict, vocab: int, rng) -> int:
    """Compile (or load) every program the cell's traffic will run.

    One phase per prefill bucket the mix's prompts fall in, from the
    shortest: a donor prompt with the most left pads of the bucket, then
    prompts with m = 0, 1, ... whole pad blocks, one for each start of a
    first prefill chunk that a partial match on pad blocks can give.  The
    donor stays in flight while they are admitted, so their pad blocks
    match its own; each of them decodes two tokens, the donor as many as
    the phase takes, which runs the decode step at the windows of the
    bucket's positions.  Returns the number of warm-up requests."""
    lo, hi = TRAFFIC.prompt_range(mix)
    cfg = run.eng.cfg
    bs = cfg.kv_block_size
    used = sorted({bucket_of(run.buckets, n) for n in range(lo, hi + 1)})
    out_max = int(mix["output_len"]["max"])
    n, prev = 0, 0
    for b in run.buckets:
        if b in used:
            l_min, l_max = max(lo, prev + 1), min(hi, b)
            grid = min(cfg.prefill_chunk or b, b)
            starts = []
            for r in range(grid // bs):  # one m of each residue mod grid
                ms = [m for m in range(r, b // bs + 1, grid // bs)
                      if l_min <= b - bs * m <= l_max]
                starts += [b - bs * ms[0]] if ms else []
            # the donor outlives every chunk and token of the others
            ticks = sum(-(-n // grid) + 2 for n in starts) + 2
            rids = [run.submit(rng.integers(1, vocab, l_min).tolist(),
                               min(out_max, ticks))]
            rids += [run.submit(rng.integers(1, vocab, length).tolist(), 2)
                     for length in starts]
            while not all(run.done(r) for r in rids):
                run.tick()
            n += len(rids)
        prev = b
    return n


# ---------------------------------------------------------------------------
# traffic loops
# ---------------------------------------------------------------------------


def closed_loop(run: Runner, items, seconds: float, trace_s):
    """Each client sends its next request when its last one is done.  The
    window opens once every client's first request has its first token
    (the pool may hold fewer requests than there are clients)."""
    clients = sorted({it.client for it in items})
    queues = {c: [it for it in items if it.client == c] for c in clients}
    current = {}
    for c in clients:
        it = queues[c].pop(0)
        current[c] = run.submit(it.prompt, it.out_len)
    first = set(current.values())
    t_open = t_close = None
    while True:
        now = run.tick()
        for c in clients:
            if run.done(current[c]) and queues[c]:
                it = queues[c].pop(0)
                current[c] = run.submit(it.prompt, it.out_len)
        if t_open is None:
            if all(r in run.clock.out for r in first):
                t_open = time.perf_counter()
                run.mark_open(t_open, trace_s)
        elif now >= t_open + seconds:
            t_close = now
            break
        if not any(queues.values()) and all(
            run.done(r) for r in current.values()
        ):
            raise RuntimeError("the closed loop ran out of requests")
    run.stop_trace()
    return t_open, t_close, []


def open_loop(run: Runner, items, seconds: float, mix: dict, trace_s):
    """Requests are sent when due, whatever the engine is doing.  The
    window opens ``lead_in_s`` after the first arrival; after it closes
    the loop runs on (still sending) until every request due in the
    window has its first token, or ``grace_s`` has passed."""
    t0 = time.perf_counter()
    t_open = t0 + mix["lead_in_s"]
    t_close = t_open + seconds
    due_in_window = []
    i, traced = 0, False
    while True:
        now = time.perf_counter()
        if not traced and now >= t_open:
            traced = True
            run.mark_open(t_open, trace_s)
        while i < len(items) and t0 + items[i].due <= now:
            due = t0 + items[i].due
            rid = run.submit(items[i].prompt, items[i].out_len)
            run.clock.due[rid] = due
            run.clock.late = max(run.clock.late, now - due)
            if t_open <= due < t_close:
                due_in_window.append(rid)
            i += 1
        if run.eng.sched.has_work():
            now = run.tick()
        elif i < len(items):
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, t0 + items[i].due - time.perf_counter()))
        if now >= t_close:
            waiting = [r for r in due_in_window if r not in run.clock.out]
            if not waiting or now >= t_close + mix["grace_s"]:
                break
        if i >= len(items) and not run.eng.sched.has_work():
            break
    run.stop_trace()
    return t_open, t_close, due_in_window


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def padded(prompt, bucket: int, pad: int) -> list[int]:
    return [pad] * (bucket - len(prompt)) + list(prompt)


def token_keys(serve_seed: int, rid: int, n: int):
    """The sampler keys of a request's first n tokens: the engine folds
    the request id into its seed's key, then each token's index."""
    base = jax.random.fold_in(jax.random.PRNGKey(serve_seed), rid)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(np.arange(n))


def pick_sample(finished: list[int], outs: dict, k: int, rng) -> list[int]:
    """k finished requests drawn from the seed, the longest among them."""
    longest = max(finished, key=lambda r: (len(outs[r]), -r))
    rest = [r for r in finished if r != longest]
    extra = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(extra)]


def gap_stats(g: np.ndarray) -> dict:
    return {"max": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.percentile(g, 99))}


def check(conf: dict, arch, weights, prompts: dict, outs: dict, sample,
          buckets, serve_seed: int, control: bool) -> dict:
    """Gaps of the sampled requests' served tokens under the reference of
    architecture ``arch`` (and, with ``control``, of the control's own
    choices at the same positions)."""
    model, as_run = conf["model"], conf["as_run"]
    wta = conf.get("wta") if conf["sampler"] == "wta" else None
    pad = int(conf["pad_token"])
    max_len = int(conf["serve"]["max_len"])
    g, gctl = [], []
    for rid in sample:
        out = outs[rid]
        b = bucket_of(buckets, len(prompts[rid]))
        seq = padded(prompts[rid], b, pad) + out[:-1]
        seq += [pad] * (max_len - len(seq))
        rows = np.arange(b - 1, b - 1 + len(out))
        z = arch.logits_rows(weights, model, as_run, seq, rows)
        keys = token_keys(serve_seed, rid, len(out)) if wta else None
        g.append(REF.gaps(z, keys, out, wta))
        if control:
            zc = arch.logits_rows(weights, model, as_run, seq, rows,
                                  variant=conf["control"])
            gctl.append(REF.gaps(z, keys, REF.control_choice(zc, keys, wta),
                                wta))
            del zc
        del z
    g = np.concatenate(g)
    res = {"gap": gap_stats(g), "tokens_compared": int(g.size),
           "requests_compared": len(sample)}
    if control:
        res["control_gap"] = gap_stats(np.concatenate(gctl))
    return res


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run_cell(cell: SPEC.Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, control: bool = False,
             cache: bool = True, check_outputs: bool = True, log=print,
             t_start: float | None = None) -> dict:
    """Set up, warm up, run the window, read the counters and the trace,
    free the engine and compare the served tokens with the reference.

    ``t_start`` is when the process began its set-up; ``control`` also
    reads the control's gap; ``check_outputs`` off skips the comparison
    (the knee sweep); ``require_tpu`` and ``cache`` off are for the CPU
    tests alone."""
    t_start = time.perf_counter() if t_start is None else t_start
    compiles = CompileLog()
    try:
        return _run_cell(cell, seed, seconds, trace, require_tpu, control,
                         cache, check_outputs, log, t_start, compiles)
    finally:
        compiles.close()


def _run_cell(cell, seed, seconds, trace, require_tpu, control, cache,
              check_outputs, log, t_start, compiles) -> dict:
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found {dev.platform}")
    if len(devs) < cell.chips:
        raise SystemExit(f"cell {cell.name} needs {cell.chips} chips, "
                         f"JAX found {len(devs)}")
    peak = RL.peaks(dev.device_kind) if require_tpu else None
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if cache:
        log(f"compile cache: {enable_compile_cache(SPEC.ROOT)}")
    get_config, params_specs, RequestState, ServeConfig, ServingEngine = (
        _program()
    )
    conf, mix, arch = cell.config, cell.traffic, cell.arch
    mc = model_config(conf, get_config, arch)
    model = conf["model"]
    vocab = arch.dims(model)["V"]
    serve_seed = seed % (2 ** 31)
    weights = jax.block_until_ready(WEIGHTS.make(arch.layout(model), seed))
    _check_layout(weights, mc, params_specs)
    scfg = ServeConfig(seed=serve_seed, **conf["serve"])
    eng = ServingEngine(weights, mc, scfg)
    buckets = scfg.buckets()
    clock = Clock()
    run = Runner(eng, clock, buckets, RequestState.DONE,
                 os.path.join(SPEC.ROOT, TRACE_DIR), compiles)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    n_warm = warm_up(run, mix, vocab, rng)
    eng.metrics()  # its first call may compile too
    warm_rids = set(clock.submit)
    items = TRAFFIC.generate(mix, vocab, seed, seconds)
    trace_s = min(seconds, float(mix.get("trace_s", seconds))) if trace else 0
    if mix["kind"] == "closed_loop":
        t_open, t_close, due = closed_loop(run, items, seconds, trace_s)
    else:
        t_open, t_close, due = open_loop(run, items, seconds, mix, trace_s)
    m_close = eng.metrics()
    jax.config.update("jax_log_compiles", False)
    in_window_compiles = compiles.compiles - run.at_open["compiles"]
    in_window_loads = compiles.cache_hits - run.at_open["loads"]
    setup_s = t_open - t_start
    mem = peak_bytes()
    log(f"setup_s={setup_s:.3f} warmup_requests={n_warm} "
        f"compiles_in_window={in_window_compiles} "
        f"cache_loads_in_window={in_window_loads} "
        f"generator_late_ms_max={clock.late * 1e3:.3f}")
    res = _end_to_end(run, t_open, t_close, due, warm_rids, setup_s)
    trace_red = spans_red = None
    if trace:
        trace_red = TR.reduce_dir(run.trace_dir)
        spans_red = SPANS.reduce_dir(run.trace_dir)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    # the check: the served requests, then the engine's state is freed
    finished = [
        r for r in clock.out
        if r not in warm_rids and run.done(r)
        and eng.sched.request(r).done_reason in ("eos", "length")
    ]
    failed_reasons = [
        eng.sched.request(r).done_reason for r in clock.submit
        if r not in warm_rids and run.done(r)
        and eng.sched.request(r).done_reason not in ("eos", "length")
    ]
    prompts = {r: list(eng.sched.request(r).prompt) for r in finished}
    outs = {r: clock.out[r] for r in finished}
    engine_outs = {r: list(eng.sched.request(r).output) for r in finished}
    del eng, run.eng
    gc.collect()
    counters = _counters(m_close, run.at_open["metrics"])
    ctx = {
        "model": model, "arch": arch, "kv_dtype": conf["kv_cache_dtype"],
        "peak": peak, "trace": trace_red, "spans": spans_red,
        "work": run.work, "counters": counters,
        "admitted_in_window": res["admitted_in_window"],
    }
    got = {"gap": {}, "tokens_compared": 0, "requests_compared": 0}
    if check_outputs:
        check_rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
        sample = pick_sample(finished, outs, int(conf["check"]["requests"]),
                             check_rng)
        t_chk = time.perf_counter()
        got = check(conf, arch, weights, prompts, outs, sample, buckets,
                    serve_seed, control)
        got["check_s"] = time.perf_counter() - t_chk
    compared = {f"{k}_gap": {"value": got["gap"][k], "limit": float(v)}
                for k, v in conf["check"]["limits"].items()
                if k in got["gap"]}
    consistent = all(engine_outs[r] == outs[r] for r in finished)
    in_vocab = all(0 <= t < vocab for o in outs.values() for t in o)
    correct = (
        check_outputs
        and all(c["value"] <= c["limit"] for c in compared.values())
        and consistent and in_vocab
        and not failed_reasons and res["attempted"] > 0
    )
    return {
        "res": res, "ctx": ctx, "check": got, "compared": compared,
        "correct": bool(correct), "consistent": consistent,
        "failed_reasons": failed_reasons, "memory_peak_bytes": mem,
        "compiles_in_window": in_window_compiles,
        "device": dev, "n_devices": len(devs), "trace": trace_red,
    }


def _counters(m_close, m0: dict) -> dict:
    """The engine's counters over the window (close minus open)."""
    m1 = vars(m_close)
    occ = lambda m: m["occupancy_mean"] * m["decode_steps"]
    return {
        "decode_steps": m1["decode_steps"] - m0["decode_steps"],
        "occupancy_sum": occ(m1) - occ(m0),
        "prefill_tokens": m1["prefill_tokens"] - m0["prefill_tokens"],
        "prefill_tokens_saved": (m1["prefill_tokens_saved"]
                                 - m0["prefill_tokens_saved"]),
    }


def _end_to_end(run, t_open, t_close, due, warm, setup_s) -> dict:
    clock = run.clock
    window = t_close - t_open
    rids = [r for r in clock.submit if r not in warm]
    out_tokens = sum(
        1 for r in rids for t in clock.tokens.get(r, []) if t_open < t <= t_close
    )
    itl = [g for r in rids
           for g in ST.gaps_within(clock.tokens.get(r, []), t_open, t_close)]
    admitted = sum(1 for r in rids
                   if clock.tokens.get(r) and t_open < clock.tokens[r][0] <= t_close)
    res = {"window_s": window, "setup_s": setup_s,
           "admitted_in_window": admitted}
    vals = {"setup_s": setup_s}
    if out_tokens:
        vals["output_tok_s"] = ST.rate(out_tokens, window)
    if itl:
        vals["itl_p99_ms"] = ST.percentile(itl, 99) * 1e3
    if due:
        # a request that never answered waited at least until the end
        t_end = max(t for ts in clock.tokens.values() for t in ts)
        ttft = [clock.tokens[r][0] - clock.due[r] if r in clock.tokens
                else t_end - clock.due[r] for r in due]
        vals["ttft_p50_ms"] = ST.percentile(ttft, 50) * 1e3
        vals["ttft_p90_ms"] = ST.percentile(ttft, 90) * 1e3
        res["missing_first_token"] = sum(1 for r in due
                                         if r not in clock.tokens)
        res["attempted"] = len(due)
    else:
        live = [r for r in rids if any(
            t_open < t <= t_close for t in clock.tokens.get(r, []))]
        res["attempted"] = len(live)
        res["missing_first_token"] = 0
    res["values"] = vals
    res["itl_samples"] = len(itl)
    res["output_tokens_in_window"] = out_tokens
    return res
