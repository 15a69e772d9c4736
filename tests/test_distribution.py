"""Distribution layer: sharding rules, small-mesh lowering (subprocess with
fake devices), elastic checkpoint reshard, serving engine."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.launch import sharding as SH
from repro.launch import roofline as RL
from repro.launch.mesh import auto_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_divisibility_guard_drops_axes():
    """heads=56 is not divisible by model=16 → replicated, not padded."""

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16))

    cfg = get_config("deepseek-coder-33b")
    rules = SH.activation_rules(FakeMesh(), cfg, 256)
    assert rules["heads"] is None          # 56 % 16 != 0
    assert rules["kv_heads"] is None       # 8 % 16 != 0
    assert rules["ffn"] == "model"
    assert rules["batch"] == ("data",)
    cfg2 = get_config("stablelm-3b")
    rules2 = SH.activation_rules(FakeMesh(), cfg2, 256)
    assert rules2["heads"] == "model"      # 32 % 16 == 0


def test_batch_axes_adapt_to_batch_size():
    class FakeMesh:
        axis_names = ("pod", "data", "model")
        devices = np.empty((2, 16, 16))

    assert SH.batch_axes(FakeMesh(), 256) == ("pod", "data")
    assert SH.batch_axes(FakeMesh(), 1) is None
    assert SH.batch_axes(FakeMesh(), 2) == ("pod",)


def test_param_rules_shard_big_models_fsdp():
    mesh = auto_mesh((1, 1), ("data", "model"))
    cfg = get_config("nemotron-4-340b")  # > FSDP threshold
    # stacked scanned-unit param: (n_units, D, F)
    sds = jax.ShapeDtypeStruct((96, 18432, 73728), jnp.bfloat16)
    sh = SH.param_shardings({"units": {"l0": {"ffn": {"w_up": sds}}}},
                            mesh, cfg)
    spec = sh["units"]["l0"]["ffn"]["w_up"].spec
    # leading scan axis None; D -> data (fsdp), F -> model
    assert spec == P(None, "data", "model")


def test_small_mesh_lowering_subprocess():
    """End-to-end dry-run machinery on an 8-device fake mesh (train +
    decode), in a subprocess so the main process keeps 1 device."""
    out = _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses
        from repro import parallel
        from repro.configs import get_smoke_config
        from repro.launch import sharding as SH, specs as SP
        from repro.train import TrainConfig, make_train_step
        from repro.configs.shapes import ShapeSpec

        cfg = get_smoke_config("gemma2-2b")
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4, 2), ("data", "model"))
        shape = ShapeSpec("t", 32, 8, "train")
        rules = SH.activation_rules(mesh, cfg, 8)
        with parallel.axis_rules(mesh, rules):
            tcfg = TrainConfig()
            st = SP.train_state_specs(cfg, tcfg)
            ssh = SH.state_shardings(st, mesh, cfg)
            bs = SP.train_batch_specs(cfg, shape)
            bsh = SH.batch_shardings(bs, mesh, 8)
            step = make_train_step(cfg, tcfg)
            c = jax.jit(step, in_shardings=(ssh, bsh),
                        out_shardings=(ssh, None),
                        donate_argnums=(0,)).lower(st, bs).compile()
            ca = c.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca  # jax<0.5
            print("TRAIN_OK", ca.get("flops", 0) > 0)

            dshape = ShapeSpec("d", 64, 8, "decode")
            ps = SP.params_specs(cfg)
            psh = SH.param_shardings(ps, mesh, cfg)
            cs = SP.decode_cache_specs(cfg, dshape)
            csh = SH.cache_shardings(cs, mesh, cfg, 8)
            from jax.sharding import NamedSharding, PartitionSpec as P
            tok = NamedSharding(mesh, P(SH.batch_axes(mesh, 8)))
            serve = SP.make_serve_step(cfg)
            c2 = jax.jit(serve, in_shardings=(psh, csh, tok),
                         out_shardings=(csh, tok),
                         donate_argnums=(1,)).lower(
                ps, cs, jax.ShapeDtypeStruct((8,), jnp.int32)).compile()
            print("DECODE_OK")
    """)
    assert "TRAIN_OK True" in out
    assert "DECODE_OK" in out


def test_elastic_checkpoint_reshard_subprocess(tmp_path):
    """Save on a 1-device run, restore sharded onto a fake 8-device mesh —
    the elastic-restart path."""
    from repro.checkpoint import save_checkpoint
    from repro.train import TrainConfig, init_train_state

    cfg = get_smoke_config("stablelm-3b")
    tcfg = TrainConfig()
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    save_checkpoint(str(tmp_path), 3, state)

    out = _run_sub(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.checkpoint import load_checkpoint
        from repro.configs import get_smoke_config
        from repro.launch import sharding as SH
        from repro.train import TrainConfig, init_train_state

        cfg = get_smoke_config("stablelm-3b")
        tcfg = TrainConfig()
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4, 2), ("data", "model"))
        like = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), cfg, tcfg))
        sh = SH.state_shardings(like, mesh, cfg)
        st = load_checkpoint({str(tmp_path)!r}, 3, like, shardings=sh)
        w = st.params["units"]["l0"]["ffn"]["w_up"]
        print("RESHARD_OK", int(st.step), w.sharding.spec)
    """)
    # restored onto the new mesh with the F axis model-sharded
    assert "RESHARD_OK 0" in out
    assert "'model'" in out


def test_roofline_collective_parser():
    hlo = """
  %ag = bf16[16,1024]{1,0} all-gather(bf16[2,1024]{1,0} %p), dims={0}
  %ar.1 = f32[512]{0} all-reduce(f32[512]{0} %x), to_apply=%sum
  %rs = f32[64]{0} reduce-scatter(f32[512]{0} %y), dimensions={0}
  %a2a = (f32[8,32]{1,0}) all-to-all(f32[8,32]{1,0} %z)
  %done = bf16[4]{0} all-gather-done(bf16[4]{0} %t)
"""
    c = RL.parse_collectives(hlo)
    assert c["all-gather"] == 16 * 1024 * 2
    assert c["all-reduce"] == 2 * 512 * 4
    assert c["reduce-scatter"] == 512 * 4
    assert c["all-to-all"] == 8 * 32 * 4
    assert c["count"] == 4  # -done is not a transfer


def test_roofline_dominant_term_tie_break():
    """Tied times must resolve by listed order (compute first), never by
    comparing the label strings ("memory" > "compute" alphabetically —
    the bug a key-less tuple max had)."""
    def rec(flops, bytes_, coll):
        return {
            "cost": {"flops": flops, "bytes accessed": bytes_},
            "collectives": {"total_bytes": coll},
            "model_flops_per_chip": flops,
        }

    # exact three-way tie: equal times for all terms → compute wins
    f = RL.PEAK_FLOPS
    t = RL.roofline_terms(rec(f, RL.HBM_BW, RL.ICI_BW))
    assert t["compute_s"] == t["memory_s"] == t["collective_s"]
    assert t["dominant"] == "compute"
    # compute/memory tie with collectives below → still compute
    t = RL.roofline_terms(rec(f, RL.HBM_BW, 0.0))
    assert t["dominant"] == "compute"
    # untied cases keep picking the true max
    assert RL.roofline_terms(rec(f, 3 * RL.HBM_BW, 0.0))[
        "dominant"
    ] == "memory"
    assert RL.roofline_terms(rec(f, 0.0, 3 * RL.ICI_BW))[
        "dominant"
    ] == "collective"


def test_serving_engine_generates():
    from repro.serving import ServeConfig, ServingEngine
    from repro.models import get_model_fns

    cfg = get_smoke_config("stablelm-3b")
    fns = get_model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, ServeConfig(max_new_tokens=5,
                                                 max_len=32))
    eng.submit([5, 6, 7])
    eng.submit([1, 2, 3, 4])
    outs = eng.step()
    assert len(outs) == 2
    assert all(len(o) == 5 for o in outs)
    assert all(0 <= t < cfg.vocab for o in outs for t in o)


def test_serving_wta_head_runs():
    import dataclasses
    from repro.serving import ServeConfig, ServingEngine
    from repro.models import get_model_fns

    cfg = dataclasses.replace(get_smoke_config("stablelm-3b"),
                              wta_head=True)
    fns = get_model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, ServeConfig(max_new_tokens=3,
                                                 max_len=32))
    eng.submit([5, 6, 7])
    outs = eng.step()
    assert len(outs[0]) == 3


def test_paged_pool_partition_specs_on_fake_mesh():
    """Directed check of the paged-pool name rules on a (data=2, model=2)
    mesh: pool pages shard over data + kv_heads over model (stablelm
    smoke, kv_heads=4), and the kv_heads axis REPLICATES when model does
    not divide it — never GSPMD padding."""
    from repro.launch import specs as SP

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((2, 2))

    cfg = get_smoke_config("stablelm-3b")  # kv_heads=4: divisible by 2
    sds = SP.paged_decode_cache_specs(cfg, batch=4, n_pages=8, block_size=8)
    specs = SH.cache_partition_specs(sds, FakeMesh(), cfg, 4)
    # (nu, n_attn, n_pages, block, Hkv, Dh)
    assert specs["k_pages"] == P(None, None, "data", None, "model", None)
    assert specs["v_pages"] == P(None, None, "data", None, "model", None)
    assert specs["pos"] == P(("data",))
    # int8 layout: scale planes follow their code pages
    import dataclasses
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    sds8 = SP.paged_decode_cache_specs(cfg8, batch=4, n_pages=8,
                                       block_size=8)
    specs8 = SH.cache_partition_specs(sds8, FakeMesh(), cfg8, 4)
    assert specs8["k_pages"] == P(None, None, "data", None, "model", None)
    assert specs8["k_scale_pages"] == P(None, None, "data", None, "model")
    assert specs8["v_scale_pages"] == P(None, None, "data", None, "model")
    assert specs8["quant_step"] == P()
    # kv_heads=1 (recurrentgemma smoke) % model=2 != 0 → heads replicate,
    # pages still shard over data
    cfg1 = get_smoke_config("recurrentgemma-2b")
    sds1 = SP.paged_decode_cache_specs(cfg1, batch=4, n_pages=8,
                                       block_size=8)
    specs1 = SH.cache_partition_specs(sds1, FakeMesh(), cfg1, 4)
    assert specs1["k_pages"] == P(None, None, "data", None, None, None)
    # a pool whose page count the data axis does not divide replicates
    sds_odd = SP.paged_decode_cache_specs(cfg, batch=4, n_pages=7,
                                          block_size=8)
    specs_odd = SH.cache_partition_specs(sds_odd, FakeMesh(), cfg, 4)
    assert specs_odd["k_pages"] == P(None, None, None, None, "model", None)


def test_sharded_engine_token_identity_subprocess():
    """Sharded-vs-unsharded token identity on real multi-device meshes
    (4 fake host devices): the full continuous-batching trace through a
    (1, model) mesh — the ISSUE's kv_heads-divisible contract — plus a
    (2, 2) mesh admission-capacity check of the data-axis pool scaling."""
    out = _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.configs import get_smoke_config
        from repro.models import get_model_fns
        from repro.serving import RequestState, ServeConfig, ServingEngine
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke_config("stablelm-3b")  # kv_heads=4: model-divisible
        params = get_model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
        prompts = [
            [163, 131, 69, 79, 11, 20, 5, 45],
            [166, 233, 129, 155, 248, 187, 162, 139],
            [239, 71, 209, 172, 1, 101],
            [142, 9, 196, 187, 216, 45, 23, 221],
        ]

        def run(mesh):
            eng = ServingEngine(params, cfg, ServeConfig(
                max_batch=2, max_new_tokens=8, max_len=64,
                kv_layout="paged", kv_block_size=8, mesh=mesh))
            for p in prompts:
                eng.submit(list(p))
            return eng.step()

        base = run(None)
        print("MODEL_MESH_OK", base == run(make_host_mesh(model=4, data=1)))
        print("DATA_MESH_OK", base == run(make_host_mesh(model=1, data=2)))
        print("GRID_MESH_OK", base == run(make_host_mesh(model=2, data=2)))

        # data-axis capacity: per-device budget 8 blocks, (2, 2) mesh pool
        # holds 16 pages at the same bytes per device
        def admitted(mesh, blocks):
            eng = ServingEngine(params, cfg, ServeConfig(
                max_batch=16, max_new_tokens=8, max_len=64,
                kv_layout="paged", kv_block_size=8, num_kv_blocks=blocks,
                enable_prefix_sharing=False, mesh=mesh))
            for _ in range(16):
                eng.submit([1, 2, 3], 8)
            eng.tick()
            return sum(1 for r in eng.sched.all_requests()
                       if r.state is not RequestState.QUEUED)

        single = admitted(None, 8)
        sharded = admitted(make_host_mesh(model=2, data=2), 16)
        print("CAPACITY_OK", sharded > single, single, sharded)
    """)
    assert "MODEL_MESH_OK True" in out
    assert "DATA_MESH_OK True" in out
    assert "GRID_MESH_OK True" in out
    assert "CAPACITY_OK True" in out


def test_kv_head_sharded_kernels_match_unsharded_subprocess():
    """The paged kernels wrapped in shard_map over a (1, 4) mesh's model
    axis (each device attends with 2 of 8 KV heads, GQA groups of 2)
    give the unsharded kernel's readout, in interpret mode: decode and
    chunked prefill, native and int8 pages."""
    out = _run_sub("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels import ops
        from repro.kernels.paged_attention import paged_attention_pallas
        from repro.kernels.prefill_attention import (
            paged_prefill_attention_pallas)
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(model=4, data=1)
        h, hkv, dh, n_pages, bs, w = 16, 8, 16, 12, 8, 4
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        kp = jax.random.normal(ks[0], (n_pages, bs, hkv, dh))
        vp = jax.random.normal(ks[1], (n_pages, bs, hkv, dh))
        kp8 = (kp * 40).astype(jnp.int8)
        vp8 = (vp * 40).astype(jnp.int8)
        scales = dict(
            k_scale=jnp.abs(jax.random.normal(ks[2], kp.shape[:3])) + 0.1,
            v_scale=jnp.abs(jax.random.normal(ks[3], kp.shape[:3])) + 0.1)
        pages = NamedSharding(mesh, P(None, None, "model", None))
        scale_sh = NamedSharding(mesh, P(None, None, "model"))
        table = jnp.asarray([[3, 7, 1, 9], [2, 11, 5, 4]], jnp.int32)
        pos = jnp.asarray([29, 13], jnp.int32)
        q_dec = jax.random.normal(ks[4], (2, h, dh))
        q_pre = jax.random.normal(ks[5], (11, h, dh))
        cases = {
            "decode": (paged_attention_pallas, q_dec, table, pos),
            "prefill": (paged_prefill_attention_pallas, q_pre, table[0],
                        jnp.asarray(16, jnp.int32)),
        }
        for name, (kernel, q, t, idx) in cases.items():
            for tag, (k, v, sc) in {
                "native": (kp, vp, {}), "int8": (kp8, vp8, scales),
            }.items():
                want = kernel(q, k, v, t, idx, interpret=True, **sc)
                sharded = ops.shard_over_kv_heads(kernel, mesh)
                sc_dev = {n: jax.device_put(a, scale_sh)
                          for n, a in sc.items()}
                got = jax.jit(functools.partial(sharded, interpret=True))(
                    q, jax.device_put(k, pages), jax.device_put(v, pages),
                    t, idx, **sc_dev)
                print("MATCH", name, tag,
                      bool(np.array_equal(np.asarray(got), np.asarray(want))))
    """)
    for name in ("decode", "prefill"):
        for tag in ("native", "int8"):
            assert f"MATCH {name} {tag} True" in out, out
