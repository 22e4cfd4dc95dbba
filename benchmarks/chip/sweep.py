"""Find the knee of an open-loop cell: the highest Poisson rate it sustains.

    python3 benchmarks/chip/sweep.py --workload chatglm3-6b.chat \
        --rates 1.5,2,2.5,3 --seconds 51 --seed 7

Runs the cell's traffic at each rate in turn, in one process, without the
correctness check, and prints one JSON line per rate: TTFT and ITL p95,
output tokens per second, how many requests due 2 s or more before the
window closed were still unadmitted at the close, and the median TTFT of
the first and last fifth of the window (after the traffic file's warm-up
arrivals).  A rate is sustained when no request failed or was preempted,
none is left unadmitted so, and the last fifth's TTFT has not grown over
the first's;
the knee is the highest such rate, and the cell runs at about four fifths
of it (the rate is written into the traffic file by hand).
"""
from __future__ import annotations

import argparse
import copy
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform not in ("tpu", "gpu"):
        raise SystemExit("sweep.py: no accelerator found")
    import generator
    import harness
    bench = harness.load_bench(ROOT)
    bench["_root"] = str(ROOT)
    cell = harness.find(bench["workloads"], args.workload, "workload")
    base = generator.load(cell["traffic"])
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = copy.deepcopy(base)
        traffic["arrival"]["rate_per_s"] = rate
        res = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                               False, time.perf_counter(), traffic=traffic,
                               do_check=False)
        print(json.dumps({"rate_per_s": rate, **res}), flush=True)


if __name__ == "__main__":
    main()
