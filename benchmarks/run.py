"""Benchmark harness entry point: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale small|tiny] [--only NAME]
                                          [--engines all|jnp,pallas_stream,...]

Prints one CSV block per benchmark and writes the full row dump to
bench_results/results.json. The roofline table itself comes from
launch/dryrun.py artifacts (EXPERIMENTS.md §Roofline), not from here.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCHES = [
    ("fig7_methods", "benchmarks.bench_lpa_methods"),
    ("fig2_k_sweep", "benchmarks.bench_k_sweep"),
    ("fig345_variants", "benchmarks.bench_variants"),
    ("pickless_rho", "benchmarks.bench_pickless"),
    ("lpa_partition", "benchmarks.bench_partition"),
    ("dist_lpa_scaling", "benchmarks.bench_dist_lpa"),
    ("grad_compression", "benchmarks.bench_compression"),
]


def _csv(rows):
    if not rows:
        return ""
    cols = []
    for r in rows:
        for k in r:
            if k not in cols:
                cols.append(k)
    lines = [",".join(cols)]
    for r in rows:
        lines.append(",".join(str(r.get(c, "")) for c in cols))
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["tiny", "small"])
    ap.add_argument("--only", default=None)
    ap.add_argument("--out", default="bench_results")
    ap.add_argument("--engines", default=None,
                    help="fold engines to time where supported: 'all' or a "
                         "comma list from the registry + 'auto' "
                         "(e.g. jnp,pallas_stream,auto)")
    ap.add_argument("--sketch", default=None,
                    help="sketch methods to sweep across --engines where "
                         "supported: 'all' or a comma list of "
                         "mg,bm,rescan (default: mg only)")
    ap.add_argument("--frontier", action="store_true",
                    help="also time frontier-gated runs where supported: "
                         "dense gated plus the sparse-compacted fold path "
                         "with skipped-row stats")
    ap.add_argument("--layout", default=None,
                    help="CSR entry layouts to time on the stream-running "
                         "backends where supported: 'all' or a comma list "
                         "of unaligned,aligned ('aligned' adds "
                         "{backend}+aligned rows with the window-aligned "
                         "layout)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    os.makedirs(args.out, exist_ok=True)
    all_rows = []
    failed = 0
    for name, module in BENCHES:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            import importlib
            import inspect
            mod = importlib.import_module(module)
            params = inspect.signature(mod.run).parameters
            kwargs = {}
            if args.engines and "engines" in params:
                kwargs["engines"] = args.engines
            if args.sketch and "sketches" in params:
                kwargs["sketches"] = args.sketch
            if args.frontier and "frontier" in params:
                kwargs["frontier"] = True
            if args.layout and "layouts" in params:
                kwargs["layouts"] = args.layout
            rows = mod.run(args.scale, **kwargs)
        except Exception as e:  # noqa: BLE001 — report and continue
            import traceback
            traceback.print_exc()
            rows = [{"bench": name, "error": f"{type(e).__name__}: {e}"}]
        failed += any("error" in r for r in rows)
        dt = time.time() - t0
        print(f"\n== {name} ({dt:.0f}s) " + "=" * max(0, 50 - len(name)))
        print(_csv(rows))
        sys.stdout.flush()
        all_rows.extend(rows)

    with open(os.path.join(args.out, "results.json"), "w") as f:
        json.dump(all_rows, f, indent=1)
    print(f"\nwrote {len(all_rows)} rows to {args.out}/results.json")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
