"""The benchmark's command: run one cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its configuration, traffic
mix, metrics and limits are found by name through ``BENCHMARK.json``
(see ``harness.py``).  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checked``: each number the
check compared, beside its limit).  Without an accelerator, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")

    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness
    bench = harness.load_bench(ROOT)
    bench["_root"] = str(ROOT)
    cell = harness.find(bench["workloads"], args.workload, "workload")

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise SystemExit(f"run.py: no accelerator found (JAX's default "
                         f"device is {devs[0].platform!r}); the benchmark "
                         f"runs only on one")
    if len(devs) < cell["chips"]:
        raise SystemExit(f"run.py: cell {args.workload!r} needs "
                         f"{cell['chips']} chips, JAX finds {len(devs)}")

    result = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
