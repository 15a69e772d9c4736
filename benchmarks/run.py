"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  bench_sigmoid        — Fig. 4 (sigmoid-neuron fidelity vs SNR knobs)
  bench_wta            — Fig. 5 (WTA vote statistics vs softmax)
  bench_fcnn_accuracy  — Fig. 6 (accuracy vs votes / threshold / SNR)
  bench_cost_model     — Table I (energy / area / TOPS-W)
  bench_kernels        — kernel micro-bench + roofline-relevant derived
  bench_serving        — WTA-vote vs greedy decode throughput
"""

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_cost_model,
        bench_fcnn_accuracy,
        bench_kernels,
        bench_serving,
        bench_sigmoid,
        bench_wta,
    )

    mods = [
        ("fig4", bench_sigmoid),
        ("fig5", bench_wta),
        ("fig6", bench_fcnn_accuracy),
        ("table1", bench_cost_model),
        ("kernels", bench_kernels),
        ("serving", bench_serving),
    ]
    print("name,us_per_call,derived")
    failed = False
    for tag, mod in mods:
        try:
            out = mod.run()
            # bench_serving returns (rows, machine-readable report)
            rows = out[0] if isinstance(out, tuple) else out
            for name, us, derived in rows:
                print(f"{tag}/{name},{us:.1f},{derived}")
                sys.stdout.flush()
        except Exception:
            failed = True
            traceback.print_exc()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
