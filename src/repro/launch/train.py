"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --smoke \
        --steps 50 --batch 8 --seq 128 [--analog] [--compress] [--model-par 1]

Runs the fault-tolerant loop (checkpoints, auto-resume, straggler monitor)
on the locally visible devices with the production sharding rules — the
same code path the multi-pod dry-run lowers, at whatever scale the host
provides (elastic: restart with any device count and the checkpoint
re-shards).
"""

from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro import parallel
from repro.configs import get_config, get_smoke_config
from repro.core.analog import AnalogConfig
from repro.core.physics import DeviceParams, calibrate_v_read
from repro.data import lm_batch, mnist_batch
from repro.launch import sharding as SH
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamWConfig
from repro.train import TrainConfig, init_train_state, make_train_step
from repro.train.loop import LoopConfig, run

logging.basicConfig(level=logging.INFO, format="%(message)s")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", action="store_true",
                    help="int8 gradient compression w/ error feedback")
    ap.add_argument("--analog", action="store_true",
                    help="RACA analog-stochastic execution (QAT)")
    ap.add_argument("--model-par", type=int, default=1,
                    help="model-parallel size on the host mesh")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.analog:
        cfg = dataclasses.replace(
            cfg,
            analog=AnalogConfig(
                mode="analog_stochastic",
                device=calibrate_v_read(DeviceParams(), cfg.d_model),
                use_pallas="auto",
            ),
        )
    tcfg = TrainConfig(
        opt=AdamWConfig(lr=args.lr),
        microbatches=args.microbatches,
        compress_grads=args.compress,
        total_steps=args.steps,
    )
    lcfg = LoopConfig(
        steps=args.steps,
        ckpt_dir=args.ckpt_dir or f"ckpts/{cfg.name}",
        ckpt_every=max(args.steps // 4, 1),
        log_every=10,
    )

    mesh = make_host_mesh(model=args.model_par)
    rules = SH.activation_rules(mesh, cfg, args.batch)
    with parallel.axis_rules(mesh, rules):
        state_sds = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(lcfg.seed), cfg, tcfg)
        )
        state_sh = SH.state_shardings(state_sds, mesh, cfg)
        step_fn = jax.jit(
            make_train_step(cfg, tcfg),
            in_shardings=(state_sh, None),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )

        if cfg.family == "fcnn":
            batch_fn = lambda s: mnist_batch(batch=args.batch, step=s)
        else:
            batch_fn = lambda s: lm_batch(
                cfg, batch=args.batch, seq=args.seq, step=s
            )
        state, stats = run(
            cfg, tcfg, lcfg, batch_fn,
            state_shardings=state_sh, step_fn=step_fn,
        )
    losses = stats["losses"]
    if losses:
        print(
            f"done: steps={int(state.step)} first_loss={losses[0][1]:.4f} "
            f"last_loss={losses[-1][1]:.4f} restarts={stats['restarts']} "
            f"stragglers={stats['stragglers']}"
        )


if __name__ == "__main__":
    main()
