"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows: for timing rows the middle
column is microseconds; for derived metrics (throughputs, utilizations,
fractions) it is empty and the value goes to the third column.
"""
import sys
import time

MODULES = [
    "benchmarks.fig1_speeds",
    "benchmarks.fig2_memory",
    "benchmarks.fig8_throughput",
    "benchmarks.table2_breakdown",
    "benchmarks.table3_ablation",
    "benchmarks.bench_engine",
    "benchmarks.bench_kernels",
    "benchmarks.roofline_report",
]


def main() -> None:
    import importlib

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(mod_name)
            rows = mod.run()
            for name, value in rows:
                if name.endswith("_us"):
                    print(f"{name},{value:.2f},")
                elif isinstance(value, str):
                    print(f"{name},,{value}")
                else:
                    print(f"{name},,{value:.6g}")
            dt = (time.perf_counter() - t0) * 1e6
            print(f"{mod_name}.total,{dt:.0f},")
        except Exception as e:                                # noqa: BLE001
            failures += 1
            print(f"{mod_name}.FAILED,,{type(e).__name__}: {e}",
                  file=sys.stdout)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
