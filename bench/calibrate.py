#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--upper-seeds 1,2,3] [--out readings.jsonl]

For every seed of ``--seeds``, the program's set-up and checked rounds run
as in a benchmark run (with a window of one round) and are compared with
the plain reference: the largest of these readings over the seeds is a
number's lower reading.  For every seed of ``--upper-seeds`` it also reads
the lower-precision control (the reference computed in bfloat16, put in
the program's place) and each planted fault of ``faults.py``: the smallest
of a variant's readings is its upper reading.  The reference with three-
and one-pass products is read beside them.  One JSON line per seed and
variant goes to standard output and to ``--out``; the summary comes last.
The benchmark's own runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: planted faults whose readings are taken; state_unchanged reads 1 by the
#: update-norm measure (no change against a change) and needs no run
READ_FAULTS = ("half_batch", "exchange_left_out", "altered_answer")
#: the reference at lower precisions, put in the program's place:
#: bfloat16 is the control of a float32 configuration with three-pass
#: products; the three-pass and one-pass products are read for the record
CONTROLS = {"control_bf16": {"dtype": "bfloat16"},
            "three_pass": {"precision": "high"},
            "one_pass": {"precision": "default"}}


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--upper-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import checks
    import faults
    import harness
    harness.use_checkout_cache(ROOT)    # the benchmark's own: runs share it
    from repro.core.plan import enable_compilation_cache

    cell = harness.load_cell(args.workload, ROOT)
    used = harness.chips_for(cell)
    enable_compilation_cache()
    meter = harness.CompileMeter()
    out = open(args.out, "a") if args.out else None
    lines = []

    def emit(seed, variant, values, side):
        line = {"cell": cell.name, "seed": seed, "variant": variant,
                "readings": values,
                "losses": {k: side[k] for k in ("local_loss", "corr_loss",
                                                "eval_loss")}}
        lines.append(line)
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    for seed in dict.fromkeys(args.seeds + args.upper_seeds):
        trained = harness.run_program(cell, used, seed, 0.0, T_PROCESS, meter)
        ref = harness.run_reference(cell, trained.arrays, seed)
        emit(seed, "program", harness.compared(trained, ref),
             trained.summary)
        if seed not in args.upper_seeds:
            continue
        for name, kw in CONTROLS.items():
            control = harness.run_reference(cell, trained.arrays, seed, **kw)
            emit(seed, name, checks.readings(control, ref), control)
        for name in READ_FAULTS:
            with faults.FAULTS[name]():
                broken = harness.run_program(cell, used, seed, 0.0,
                                             T_PROCESS, meter)
            emit(seed, name, harness.compared(broken, ref), broken.summary)

    summary = {"cell": cell.name, "lower": {}, "upper": {}}
    prog = [x["readings"] for x in lines if x["variant"] == "program"
            and x["seed"] in args.seeds]
    for k in (prog[0] if prog else {}):
        summary["lower"][k] = max(r[k] for r in prog)
    for variant in {x["variant"] for x in lines} - {"program"}:
        rs = [x["readings"] for x in lines if x["variant"] == variant]
        summary["upper"][variant] = {k: min(r[k] for r in rs)
                                     for k in rs[0]}
    print(json.dumps(summary), flush=True)
    if out:
        out.write(json.dumps(summary) + "\n")
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
