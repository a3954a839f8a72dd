# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
# Quick mode by default (subset data, 2 seeds, cached training);
# REPRO_FULL=1 reproduces the paper-scale protocol (full splits, 100
# epochs, 5 seeds).  Roofline rows read results/dryrun.jsonl.
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.kernels import enable_compile_cache
    enable_compile_cache()
    from . import tables_accuracy as acc
    from . import tables_deploy as dep
    from . import roofline_table as roof
    from . import beyond_paper as bp

    benches = [
        acc.table1_hidden_size,
        acc.table2_lsq_pipeline,
        acc.table3_per_seed,
        acc.table4_param_footprint,
        acc.table5_quant_modes,
        acc.fig6_per_class,
        dep.table6_bitequiv,
        dep.table7_streaming,
        dep.table89_energy,
        dep.warmup_latency,
        dep.lut_speedup,
        bp.dual_rank_decomposition,       # paper Sec. VI-E direction 1
        bp.warmup_lstm_gru,               # paper Sec. VI-A follow-up
        roof.roofline_table,
    ]
    print("name,us_per_call,derived")
    failures = 0
    for b in benches:
        try:
            for row in b():
                print(row, flush=True)
        except Exception:
            failures += 1
            print(f"{b.__name__},ERROR,{traceback.format_exc(limit=1)!r}",
                  flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
