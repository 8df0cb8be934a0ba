"""A throwaway checkout for the harness on the CPU: the real ``bench/``
copied, plus a tiny cell added as new files only (a configuration, a mix
and two metric readers), the way a later change adds a cell."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {"family": "rmat",
               "params": {"scale": 7, "edge_factor": 8, "a": 0.57,
                          "b": 0.19, "c": 0.19},
               "base_seed": 3}
# the streamed engine on a graph this small: a VMEM budget of 1 KiB
TINY_MIX = {"lpa_config": {"method": "mg", "k": 8, "chunk": 128, "rho": 8,
                           "tau": 0.05, "max_iters": 20,
                           "fold_backend": "auto", "aligned_layout": True,
                           "vmem_budget_bytes": 1024},
            "require_backend": "pallas_stream"}
EDGES_READER = '''"""Directed edge slots of the cell's graph."""


def read(r):
    return float(r.graph.n_edges)
'''
SLOTS_READER = '''"""Window slots of the streamed plan."""


def read(r):
    return float(r.window_slots) if r.trace is not None else None
'''


def make_checkout(tmp: str) -> str:
    """Returns the root of a checkout with the tiny cell ``tiny.mg8s``."""
    shutil.copytree(BENCH, os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny.mg8s", "config": "tiny",
                              "traffic": "mg8s", "chips": 1,
                              "why": "tiny"})
    spec["end_to_end"].append({"name": "edges", "unit": "slots",
                               "better": "lower", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["tiny.mg8s"]})
    spec["per_layer"].append({"name": "slots", "unit": "slots",
                              "better": "lower", "source": "program_counter",
                              "layer": "plan", "moves": "iteration_ms",
                              "workloads": ["tiny.mg8s"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    for rel, body in (("configs/tiny.json", json.dumps(TINY_CONFIG)),
                      ("mixes/mg8s.json", json.dumps(TINY_MIX)),
                      ("metrics/edges.py", EDGES_READER),
                      ("metrics/slots.py", SLOTS_READER)):
        with open(os.path.join(tmp, "bench", rel), "w") as f:
            f.write(body)
    return tmp
