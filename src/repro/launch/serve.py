"""Serving launcher: continuous-batching engine against a (randomly
initialized or checkpointed) model, greedy or WTA-stochastic sampling.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b --smoke \
        --requests 4 --new-tokens 16 [--wta] [--static] \
        [--ckpt-dir ckpts/stablelm-3b]
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro.checkpoint import latest_step, load_checkpoint
from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model_fns
from repro.serving import ServeConfig, ServingEngine, StaticServingEngine


def device_line() -> str:
    """The device the run is on, as JAX reports it."""
    d = jax.devices()
    return (
        f"device: platform={d[0].platform} kind={d[0].device_kind} "
        f"count={len(d)}"
    )


def init_params(cfg, seed: int = 0):
    """Seeded random params, built in one jitted program (at published
    widths an op-by-op init dispatches thousands of small ops)."""
    fns = get_model_fns(cfg)
    return jax.jit(lambda key: fns.init(key, cfg))(jax.random.PRNGKey(seed))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--wta", action="store_true",
                    help="WTA stochastic SoftMax sampling (the paper's head)")
    ap.add_argument("--static", action="store_true",
                    help="static-batch reference engine (no slot refill)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (continuous-batching batch width)")
    ap.add_argument("--kv-layout", choices=("paged", "dense"),
                    default="paged",
                    help="KV cache layout: paged block pool (default) or "
                         "the dense per-slot max_len oracle")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV block (paged layout)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="pool size in blocks; 0 = dense-parity capacity")
    ap.add_argument("--kv-dtype", choices=("same", "int8"), default="same",
                    help="KV cache dtype: 'int8' stores stochastically "
                         "rounded int8 codes + scale planes (half the "
                         "decode HBM bytes; doubled paged-pool capacity)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable content-hash prompt-block sharing with "
                         "copy-on-write in the paged pool")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="max prefill tokens computed per engine tick "
                         "(paged layout; 0 = whole bucket at once); also "
                         "the partial-prefix resume grid for "
                         "recurrent/SSM families")
    ap.add_argument("--sharded", action="store_true",
                    help="shard the paged pool + decode step over a "
                         "(data, model) mesh of the local devices (use "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N"
                         " for a multi-device CPU mesh)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis size of the serving mesh (--sharded); "
                         "remaining devices go to the data axis")
    ap.add_argument("--priority", type=int, default=1,
                    help="priority class for the submitted requests: 0 = "
                         "interactive (may preempt lower classes under "
                         "pool pressure, spilling their pages to host), "
                         "1 = batch (default)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline in ms from submission; a "
                         "request past it is evicted with reason "
                         "'deadline' (default: none)")
    ap.add_argument("--no-preemption", action="store_true",
                    help="disable priority preemption (higher-priority "
                         "arrivals back-pressure instead of spilling a "
                         "lower-priority victim's KV pages to host)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="self-speculative decoding: draft up to K tokens "
                         "per tick with the fused decode step, verify the "
                         "run in one read-only pass, roll back at the "
                         "first mismatch (paged layout; 0 = off; greedy "
                         "streams are byte-identical to plain decode)")
    ap.add_argument("--spill-budget-bytes", type=int, default=None,
                    help="cap on host bytes held by preemption spill "
                         "records; oldest records are dropped at the cap "
                         "and their victims recompute from the prompt on "
                         "restore (default: unbounded)")
    ap.add_argument("--device-backend", default="sim",
                    help="analog device backend: 'sim' (ideal math) or "
                         "'sim_faulty' (seeded ReRAM fault model: stuck "
                         "cells, conductance drift, readout noise)")
    ap.add_argument("--stuck-rate", type=float, default=0.0,
                    help="fraction of crossbar cells stuck at SA0/SA1 "
                         "(sim_faulty; split evenly between the rails)")
    ap.add_argument("--drift-nu", type=float, default=0.0,
                    help="conductance drift exponent: multiplier "
                         "(1+clock)^-nu on the fault clock (sim_faulty)")
    ap.add_argument("--read-sigma-inflation", type=float, default=0.0,
                    help="fractional inflation of comparator read-noise "
                         "sigma (sim_faulty)")
    ap.add_argument("--comparator-offset", type=float, default=0.0,
                    help="additive comparator threshold offset in "
                         "normalized units (sim_faulty)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the deterministic stuck-cell maps "
                         "(sim_faulty)")
    ap.add_argument("--canary-interval", type=int, default=0,
                    help="run a known-answer crossbar canary probe every "
                         "N engine ticks (0 = off); failures feed the "
                         "degradation ladder and tile retirement")
    ap.add_argument("--n-redundant-reads", type=int, default=1,
                    help="baseline comparator re-reads per WTA decode "
                         "sample, majority-voted (1 = single read)")
    ap.add_argument("--tile-retire-threshold", type=float, default=0.0,
                    help="retire crossbar tiles whose stuck-cell density "
                         "exceeds this fraction after a canary failure "
                         "(0 = never retire)")
    ap.add_argument("--degrade", action="store_true",
                    help="enable the graceful-degradation ladder "
                         "(disable speculation -> raise redundant reads "
                         "-> shed batch admissions) driven by canary "
                         "failures and sanity evictions")
    ap.add_argument("--ckpt-dir")
    args = ap.parse_args()
    print(device_line())
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(
        cfg, wta_head=args.wta, kv_cache_dtype=args.kv_dtype
    )
    params = init_params(cfg)
    if args.ckpt_dir:
        step = latest_step(args.ckpt_dir)
        if step is not None:
            like = jax.eval_shape(lambda: params)
            state = load_checkpoint(args.ckpt_dir, step, like)
            params = state  # params-only checkpoints
            print(f"loaded checkpoint step {step}")

    mesh = None
    if args.sharded:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(model=args.mesh_model)
        print(f"serving mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    fault_cfg = None
    if args.device_backend == "sim_faulty":
        from repro.kernels.backend import FaultConfig

        fault_cfg = FaultConfig(
            seed=args.fault_seed,
            stuck_rate=args.stuck_rate,
            drift_nu=args.drift_nu,
            read_sigma_inflation=args.read_sigma_inflation,
            comparator_offset=args.comparator_offset,
        )
    degradation = None
    if args.degrade:
        from repro.serving import DegradationPolicy

        degradation = DegradationPolicy()

    engine_cls = StaticServingEngine if args.static else ServingEngine
    eng = engine_cls(
        params, cfg,
        ServeConfig(
            max_batch=args.slots,
            max_new_tokens=args.new_tokens,
            max_len=args.max_len,
            kv_layout=args.kv_layout,
            kv_block_size=args.kv_block_size,
            num_kv_blocks=args.kv_blocks,
            enable_prefix_sharing=not args.no_prefix_sharing,
            # passed through verbatim: ServeConfig.validate raises loudly
            # on --kv-layout dense + --prefill-chunk (paged-only knob)
            prefill_chunk=args.prefill_chunk,
            enable_preemption=not args.no_preemption,
            speculate_k=args.speculate_k,
            spill_budget_bytes=args.spill_budget_bytes,
            mesh=mesh,
            device_backend=args.device_backend,
            device_fault_config=fault_cfg,
            canary_interval=args.canary_interval,
            n_redundant_reads=args.n_redundant_reads,
            tile_retire_threshold=args.tile_retire_threshold,
            degradation=degradation,
        ),
    )
    rng = jax.random.PRNGKey(7)
    submit_kw = {}
    if not args.static:
        # the static reference engine has no scheduler: priority and
        # deadline are continuous-engine concepts
        submit_kw = dict(
            priority=args.priority, deadline_ms=args.deadline_ms
        )
    for i in range(args.requests):
        rng, k = jax.random.split(rng)
        n = int(jax.random.randint(k, (), 2, 9))
        prompt = jax.random.randint(k, (n,), 0, cfg.vocab).tolist()
        eng.submit(prompt, **submit_kw)
    t0 = time.time()
    # drain everything: the static engine's step() serves only one
    # max_batch wave, so both engines go through their full-drain APIs
    outs = eng.run() if args.static else eng.step()
    dt = time.time() - t0
    m = eng.metrics()
    total = sum(len(o) for o in outs)
    print(
        f"served {len(outs)} requests, {total} tokens in {dt:.2f}s "
        f"({total / max(dt, 1e-9):.1f} tok/s, ttft {m.ttft_mean * 1e3:.0f}ms"
        f" p99 {m.ttft_p99 * 1e3:.0f}ms,"
        f" occupancy {m.occupancy_mean:.2f}, prefix hits {m.prefix_hits},"
        f" partial hits {m.prefix_partial_hits},"
        f" prefill tokens saved {m.prefill_tokens_saved},"
        f" preemptions {m.preemptions} (restores {m.restores}),"
        f" engine={'static' if args.static else 'continuous'}, sampler="
        f"{'WTA votes' if args.wta else 'greedy'})"
    )
    if m.evictions:
        print("evictions:", ", ".join(
            f"{k}={v}" for k, v in sorted(m.evictions.items())
        ))
    if m.spec_rounds:
        print(
            f"speculative: {m.spec_rounds} rounds, "
            f"{m.spec_drafted} drafted / {m.spec_accepted} accepted "
            f"(acceptance {m.spec_acceptance:.2f}, "
            f"{m.spec_tokens_per_round:.2f} tokens/round); "
            f"spill drops {m.spill_drops}"
        )
    for pr, row in sorted(m.latency_by_class.items()):
        print(
            f"class {pr}: n={row['n']} "
            f"ttft p50/p99 {row['ttft_p50_ms']:.0f}/"
            f"{row['ttft_p99_ms']:.0f}ms, "
            f"latency p50/p99 {row['latency_p50_ms']:.0f}/"
            f"{row['latency_p99_ms']:.0f}ms"
        )
    if m.canary_probes or m.degraded_mode or m.degraded_transitions:
        print(
            f"fault tolerance: degraded_mode {m.degraded_mode}, "
            f"canary {m.canary_failures}/{m.canary_probes} failed, "
            f"retired tiles {m.retired_tiles}, "
            f"redundant reads {m.redundant_read_events}, "
            f"transitions {len(m.degraded_transitions)}"
        )
    if m.analog:
        tc = m.analog["tokens_computed"]
        print(
            f"energy (Table I pricing, {m.analog['backend']} backend): "
            f"computed {tc['total']} tokens "
            f"(prefill {tc['prefill']}, decode {tc['decode']}, "
            f"draft {tc['draft']}) for {m.analog['tokens_published']} "
            f"published; "
            f"RACA {m.analog['raca']['energy_pj_per_token']:.0f} pJ/tok "
            f"({m.analog['raca']['tops_per_w_effective']:.2f} TOPS/W), "
            f"1b-ADC {m.analog['adc1b']['energy_pj_per_token']:.0f} pJ/tok "
            f"({m.analog['adc1b']['tops_per_w_effective']:.2f} TOPS/W)"
        )
    for o in outs:
        print("  ->", o)


if __name__ == "__main__":
    main()
