"""Readings for a cell's limits: the program's gap on many seeds, and the
control's on the first few, in one process.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 11,12,...,22 --control-seeds 4 --seconds 25

For each seed it runs the cell once (set-up, window, check) and prints one
JSON line: the widest gap of a served token below the reference's best
logit (the program's reading).  On the first ``--control-seeds`` seeds the
control takes the program's place in the comparison: the tokens that the
reference puts first when its linear layers take 4-bit activations (int4
for the configuration's int8), at the same positions of the same requests,
are judged against the limit, and ``correct`` has to come out false.  The
limit in ``limits/<cell>.json`` is set from these two readings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--control-bits", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=25)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform not in ("tpu", "gpu"):
        raise SystemExit("control.py: no accelerator found")
    import harness
    bench = harness.load_bench(ROOT)
    bench["_root"] = str(ROOT)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        bits = args.control_bits if i < args.control_seeds else None
        res = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, time.perf_counter(), control_bits=bits)
        print(json.dumps({
            "seed": seed, "control_bits": bits, "correct": res["correct"],
            "failed": res["failed"],
            "compilations_in_window": res["compilations_in_window"],
            "program": res["program"]["served_logit_gap_max"],
            "program_mean": res["program"]["served_logit_gap_mean"],
            "judged": res["checked"]["served_logit_gap_max"]["value"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()}}),
            flush=True)


if __name__ == "__main__":
    main()
