"""Benchmark entry point: ``PYTHONPATH=src python -m benchmarks.run``.

Prints ``name,us_per_call,derived`` CSV — one section per paper artifact
(Fig. 2/4, Table 1, Fig. 5, Fig. 6, the κ-vs-gap study), the kernel
micro-benchmarks, and the roofline rows if a dry-run has been recorded.

``--fast`` trims the round counts (used by CI); the full run takes a few
minutes on this container.
"""
from __future__ import annotations

import argparse
import sys
import time


def _emit(rows):
    for r in rows:
        if "name" in r:
            print(f"{r['name']},{r.get('us_per_call', 0):.1f},"
                  f"{r.get('derived','')}")
        else:
            name = "_".join(str(r.get(k)) for k in
                            ("figure", "strategy", "arch", "partition", "K",
                             "fanout", "S", "round") if r.get(k) is not None)
            val = r.get("val_score", r.get("final_score", r.get("gap_closed", 0)))
            derived = ";".join(f"{k}={v}" for k, v in r.items()
                               if k not in ("figure", "name"))
            print(f"{name},{float(val) * 1e6 if val == val else 0:.1f},{derived}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,table1,fig5,fig6,kappa,kernels,"
                         "engine,comm,ckpt,roofline")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    rounds = 4 if args.fast else 8

    from benchmarks import paper_experiments as P
    from benchmarks import kernel_bench as K
    from repro.core import enable_compilation_cache

    enable_compilation_cache()

    t0 = time.time()
    print("name,us_per_call,derived")
    if only is None or "fig2" in only:
        _emit(P.fig2_and_fig4(rounds=rounds))
    if only is None or "table1" in only:
        _emit(P.table1(rounds=max(rounds - 2, 3)))
    if only is None or "fig5" in only:
        _emit(P.fig5_local_K(rounds=rounds))
    if only is None or "fig6" in only:
        _emit(P.fig6_sampling(rounds=max(rounds - 2, 3)))
    if only is None or "kappa" in only:
        _emit(P.kappa_vs_gap(rounds=max(rounds - 2, 3)))
    if only is None or "yelp" in only:
        _emit(P.yelp_regime(rounds=max(rounds - 2, 3)))
    if only is None or "fig11" in only:
        _emit(P.fig11_subgraph_approx(rounds=max(rounds - 2, 4)))
    if only is None or "scaling" in only:
        _emit(P.machines_scaling(rounds=max(rounds - 2, 4)))
    if only is None or "kernels" in only:
        _emit(K.all_rows())
    if only is None or "engine" in only:
        from benchmarks import engine_bench as E
        _emit(E.rows())
    if only is None or "comm" in only:
        from benchmarks import comm_bench as C
        _emit(C.rows())
    if only is None or "ckpt" in only:
        from benchmarks import ckpt_bench as CK
        _emit(CK.rows())
    if only is None or "roofline" in only:
        try:
            from benchmarks.roofline import rows_for_run
            _emit(rows_for_run())
        except Exception as e:  # noqa: BLE001
            print(f"roofline_skipped,0,{type(e).__name__}")
    print(f"# total_benchmark_wall_s={time.time() - t0:.1f}", file=sys.stderr)


if __name__ == "__main__":
    main()
