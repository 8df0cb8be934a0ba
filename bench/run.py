"""Runs one benchmark cell once on the chip and prints its result.

    python3 bench/run.py --workload graph500.mg8 --seed 7 --seconds 30 --trace 0

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the root of
the checkout; ``bench/benchlib/harness.py`` says what one run does. The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also ``breakdown``,
and last ``checks``: each number compared with its limit). The checks are
also the last lines of standard error. Without as many TPU chips as the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax
    from benchlib import harness
    from repro.launch.compile_cache import use_compile_cache
    bench = harness.Bench()
    use_compile_cache()
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = harness.run(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, bench=bench)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
