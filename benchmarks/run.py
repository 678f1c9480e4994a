"""Benchmark harness — one function per paper table/figure, plus the
beyond-paper pod-scale sweep.  Prints ``name,us_per_call,derived`` CSV
(after the human-readable artifacts).

    PYTHONPATH=src python -m benchmarks.run [--only fig7] [--quick]
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--quick", action="store_true",
                    help="skip the measured (wall-clock) benches")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import codec_bench as C
    from . import energy_front as E
    from . import kway_runtime as K
    from . import paper_tables as P
    from . import replica_bench as R
    from . import serve_bench as SG
    from . import stream_bench as S
    from . import tpu_pod_pareto as T
    from . import transport_bench as TR

    benches = {
        "table1": P.table1_models,
        "fig2": P.fig2_blockwise,
        "fig3": P.fig3_pareto_pi_pi,
        "fig4": P.fig4_pareto_pi_gpu,
        "fig56": P.fig56_duress,
        "fig7": P.fig7_backends,
        "table23": P.table23_breakdown,
        "pod_pareto": T.pod_pareto,
        "kway_front": K.kway_front,
        "kway_adaptive": K.kway_adaptive,
        "energy_front": E.energy_front,
        "pareto_bench": E.pareto_bench,
        "transport_overhead": TR.transport_overhead,
        "stream_session": S.stream_throughput,
        "codec_overhead": C.codec_overhead,
        "replica_fanout": R.run,
        "serve_gateway": SG.serve_throughput,
    }
    measured = {"fig2", "fig7", "kway_front", "kway_adaptive",
                "transport_overhead", "stream_session", "codec_overhead",
                "replica_fanout", "serve_gateway"}
    rows: list[str] = []
    for name, fn in benches.items():
        if args.only and args.only not in name:
            continue
        if args.quick and name in measured:
            continue
        try:
            rows.extend(fn())
        except Exception as e:  # surface but keep the harness going
            print(f"[bench {name} FAILED] {type(e).__name__}: {e}",
                  file=sys.stderr)
            rows.append(f"{name}/FAILED,0.0,{type(e).__name__}")

    print("\nname,us_per_call,derived")
    for r in rows:
        print(r)


if __name__ == "__main__":
    main()
