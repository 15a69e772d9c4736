"""Compiles a configuration's decode step and one prefill chunk for a
described TPU v5e chip, with no chip attached.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config deepseek-coder-33b-pp8

The programs are the engine's own (``make_paged_serve_step`` and
``make_paged_suffix_prefill``) at the configuration's serving sizes: the
whole pool, every slot, the widest decode window and the largest prefill
bucket.  The serving path picks its Pallas kernels only on a TPU backend,
so this process tells it that it is on one.  Prints each program's
compile seconds, whether it holds a Pallas kernel, and XLA's memory
analysis.  Nothing runs: it says nothing about times or results.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--chunk", type=int, default=0,
                    help="prefill chunk length (default: the config's)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness, spec

    jax.config.update("jax_enable_compilation_cache", False)
    conf = spec.load_json(spec.config_file(args.config))
    get_config, params_specs, _, ServeConfig, _ = harness._program()
    from repro.kernels import ops
    from repro.launch import specs as SP

    ops.tpu_kernels = lambda: True
    mc = harness.model_config(conf, get_config, spec.arch_of(conf))
    scfg = ServeConfig(**conf["serve"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    b = scfg.max_batch
    pages = scfg.pool_blocks(mc.kv_cache_dtype)
    params = on(params_specs(mc))
    cache = on(SP.paged_decode_cache_specs(mc, b, pages, scfg.kv_block_size))
    w = scfg.max_kv_blocks()
    bucket = max(scfg.buckets())
    chunk = args.chunk or scfg.prefill_chunk or bucket
    progs = {
        "serve_step": (
            jax.jit(SP.make_paged_serve_step(mc), donate_argnums=(1,)),
            (params, cache, sds((b, w), jnp.int32), sds((b,), jnp.int32),
             sds((b, 2), jnp.uint32), sds((b,), jnp.int32)), {},
        ),
    }
    pre = [params, cache, on(jax.eval_shape(lambda: SP.init_prefill_state(mc))),
           sds((1, chunk), jnp.int32), sds((bucket // scfg.kv_block_size,),
                                           jnp.int32), sds((), jnp.int32)]
    if mc.kv_cache_dtype == "int8":
        pre.append(sds((chunk // scfg.kv_block_size,), jnp.uint32))
    progs["prefill_chunk"] = (
        jax.jit(SP.make_paged_suffix_prefill(mc), static_argnames=("bucket",),
                donate_argnums=(1,)),
        tuple(pre), {"bucket": bucket},
    )
    for name, (fn, shapes, kw) in progs.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*shapes, **kw).compile()
        secs = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        print(json.dumps({
            "program": name, "config": args.config, "compile_s": secs,
            "pallas_calls": compiled.as_text().count("tpu_custom_call"),
            "slots": b, "pages": pages, "window_blocks": w,
            "bucket": bucket, "chunk": chunk,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
