"""Runs the controls of a cell: the plain reference with one stated
guarantee cut, in the program's place, judged by the cell's comparison.

    python3 bench/controls.py --workload kmer.mg8 --seeds 1,2,3

For each seed: the cell's graph (from the cache or generated), the
reference as the configuration states it, then the configuration's
``control`` (or each of ``--controls``, named in
``benchlib.reference.CONTROLS``); prints one JSON line per seed and control
with the numbers the comparison reads, and whether the comparison has to
reject it. Host only: it needs no chip.
"""
from __future__ import annotations

import argparse
import json
import time

from benchlib import graphs, harness, reference


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default=None)
    args = ap.parse_args()
    bench = harness.Bench()
    cell = bench.workload(args.workload)
    config, mix = bench.config(cell["config"]), bench.mix(cell["traffic"])
    names = (args.controls.split(",") if args.controls
             else [config["control"]])
    for seed in [int(s) for s in args.seeds.split(",")]:
        g, _ = graphs.load_or_generate(config, seed)
        t0 = time.perf_counter()
        ref = harness.reference_solve(g, mix)
        ref_s = time.perf_counter() - t0
        for name in names:
            c = harness.reference_solve(g, mix, **reference.CONTROLS[name])
            checks, failed = harness.compare(
                [harness.Solve(c.labels, c.iterations, c.changed_history)],
                ref)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "control": name,
                "must_reject": name == config["control"],
                "rejected": failed > 0, "reference_s": ref_s,
                "reference_iterations": ref.iterations,
                "checks": {k: v["value"] for k, v in checks.items()}}),
                flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
