"""Benchmark harness entry point: one section per paper table/figure and per
subsystem benchmark.  Prints ``name,value,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run [--only fig5,fig6,...]
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of fig5,fig6,fig7,table1,kernels,"
                         "kernel_batching,streaming_fusion,wdm_streaming,"
                         "composed_reservoirs,dfr_serving,chaos_soak,"
                         "device_sweep")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (chaos_soak, composed_reservoirs, device_sweep, dfr_serving,
                   fig5_nrmse, fig6_ser, fig7_training_time, kernel_batching,
                   kernel_bench, streaming_fusion, table1_power, wdm_streaming)

    sections = {
        "fig5": fig5_nrmse.run,
        "fig6": fig6_ser.run,
        "fig7": fig7_training_time.run,
        "table1": table1_power.run,
        "kernels": kernel_bench.run,
        "kernel_batching": kernel_batching.run,
        "streaming_fusion": streaming_fusion.run,
        "wdm_streaming": wdm_streaming.run,
        "composed_reservoirs": composed_reservoirs.run,
        "dfr_serving": dfr_serving.run,
        "chaos_soak": chaos_soak.run,
        "device_sweep": device_sweep.run,
    }
    chosen = args.only.split(",") if args.only else list(sections)
    print("name,value,derived")
    failed = 0
    for name in chosen:
        t0 = time.time()
        try:
            for row in sections[name]():
                print(row)
        except Exception as e:  # noqa: BLE001 — report and continue
            failed += 1
            print(f"{name}/ERROR,{type(e).__name__},{e}")
        print(f"{name}/elapsed_s,{time.time()-t0:.1f},", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
