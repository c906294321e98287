# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure plus the roofline
report and real measured serving/kernel benches.

    PYTHONPATH=src python -m benchmarks.run [--only substring]
"""
import argparse
import sys
import traceback

from repro.compile_cache import use_compile_cache

from . import (
    ablation_dse,
    adaptive_replan,
    eq12_design_space,
    fault_recovery,
    fleet_serving,
    fig3_kernel_level,
    fig5_disproportionate,
    fig6_conv_share,
    fig7_layer_times,
    fig8_two_stage,
    fig9_three_stage,
    fig11_concavity,
    fig13_quantization,
    kernels_bench,
    multimodel_serving,
    power_aware,
    roofline_report,
    serving_pipeline,
    table3_prediction_error,
    table4_throughput,
    table56_configs,
    tail_latency,
    tpu_pipeit_bench,
)

MODULES = [
    eq12_design_space,
    ablation_dse,
    fig3_kernel_level,
    fig5_disproportionate,
    fig6_conv_share,
    fig7_layer_times,
    fig8_two_stage,
    fig9_three_stage,
    fig11_concavity,
    table3_prediction_error,
    table4_throughput,
    table56_configs,
    fig13_quantization,
    serving_pipeline,
    multimodel_serving,
    adaptive_replan,
    power_aware,
    tail_latency,
    fault_recovery,
    fleet_serving,
    kernels_bench,
    tpu_pipeit_bench,
    roofline_report,
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    use_compile_cache()
    print("name,us_per_call,derived")
    failed = 0
    for mod in MODULES:
        name = mod.__name__.split(".")[-1]
        if args.only and args.only not in name:
            continue
        try:
            for row in mod.run():
                print(row)
        except Exception as e:
            failed += 1
            print(f"{name},0.00,ERROR {type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
