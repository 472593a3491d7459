# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows.  Expensive substrate (trained server model, profiling dataset,
# the fig-8 simulation grid) is cached under benchmarks/artifacts/cache.
import sys
import time


def main(argv=None) -> int:
    from repro import compile_cache
    compile_cache.enable()
    from benchmarks import (bench_backbone, bench_multiclient, bench_quant,
                            bench_reuse, bench_robustness, bench_serving,
                            fig5_restoration, fig8_overall, fig9_delays,
                            fig10_codec, fig11_overhead, fig12_ablation,
                            roofline, table2_estimator)

    only = set(argv[1:]) if argv and len(argv) > 1 else None
    suites = [
        ("bench_backbone", bench_backbone),
        ("bench_multiclient", bench_multiclient),
        ("bench_quant", bench_quant),
        ("bench_reuse", bench_reuse),
        ("bench_serving", bench_serving),
        ("bench_robustness", bench_robustness),
        ("fig5", fig5_restoration),
        ("table2", table2_estimator),
        ("fig8", fig8_overall),
        ("fig9", fig9_delays),
        ("fig10", fig10_codec),
        ("fig11", fig11_overhead),
        ("fig12", fig12_ablation),
        ("roofline", roofline),
    ]
    ctx: dict = {}
    print("name,us_per_call,derived")
    t_start = time.time()
    failed = 0
    for name, mod in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = mod.run(ctx)
        except Exception as e:  # a failing suite is a bug; keep going
            failed += 1
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}")
            continue
        for r in rows:
            nm, us, derived = r
            print(f"{nm},{us:.1f},{derived}")
        print(f"{name}/_wall,{(time.time() - t0) * 1e6:.0f},suite wall time",
              flush=True)
    print(f"total/_wall,{(time.time() - t_start) * 1e6:.0f},full harness")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
