"""The harness on the CPU with a four-shard cell: a tiny weighted Graph500
cell with the ``halo`` mix's four-shard ``LPAConfig``, added to a throwaway
checkout as new files only, runs through ``build_workspace()`` and
``lpa()`` on four virtual CPU devices. A sound run is correct; a run with
one shard's labels frozen is not; a traced run reads the two per-layer
metrics of the four-chip cell.

Each run runs in a subprocess of its own: the four devices must be set
before JAX starts, and this process keeps its one. A CPU trace has no
device planes, so the readers of the four-chip cell's per-layer metrics
are checked on a traced solve recorded on a four-chip TPU v5e host
(``data/trace_graph500_4chip_s21.json.gz``: the cell at seed 2147484001,
its device events and ``lpa.*`` spans), and on the CPU only for reading
nothing without raising."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import harness, trace  # noqa: E402

SEED = 2**31 + 77
# scale 8, weighted; chunk 16 and k 4 give its hub rows several fold
# rounds, and a VMEM budget of 1 KiB makes "auto" stream
TINY_CONFIG = {"family": "rmat",
               "params": {"scale": 8, "edge_factor": 8, "a": 0.57,
                          "b": 0.19, "c": 0.19, "weighted": True},
               "base_seed": 3}
with open(os.path.join(BENCH, "mixes", "halo.json")) as _f:
    TINY_MIX = json.load(_f)
TINY_MIX["lpa_config"].update(k=4, chunk=16, stream_window=512,
                              vmem_budget_bytes=1024)

CHILD = """
import json, sys, time
sys.path[:0] = [{bench!r}, {src!r}]
from benchlib import harness
bench = harness.Bench({root!r})
fault = {fault!r}
if fault == "frozen_shard":
    from repro.core import distributed
    orig = distributed.dist_lpa_step

    def frozen(mesh, ws, **kw):
        step = orig(mesh, ws, **kw)

        def s(*a, **k):
            out = step(*a, **k)
            return (out[0].at[0].set(a[4][0]),) + tuple(out[1:])
        return s
    distributed.dist_lpa_step = frozen
out = harness.run("tiny4.halo", {seed}, 0.2, {trace},
                  t_start=time.perf_counter(), bench=bench, require_tpu=False)
print("RESULT", json.dumps(out))
"""


def make_checkout(tmp: str) -> str:
    """A copy of ``bench/`` and ``BENCHMARK.json`` plus the four-chip cell
    ``tiny4.halo`` (a configuration and a mix as new files)."""
    import shutil
    shutil.copytree(BENCH, os.path.join(tmp, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__",
                                                  "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny4.halo", "config": "tiny4",
                              "traffic": "tiny4", "chips": 4,
                              "why": "tiny"})
    for m in spec["per_layer"]:
        if "graph500_4chip.halo" in m.get("workloads", []):
            m["workloads"].append("tiny4.halo")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    for rel, body in (("configs/tiny4.json", TINY_CONFIG),
                      ("mixes/tiny4.json", TINY_MIX)):
        with open(os.path.join(tmp, "bench", rel), "w") as f:
            json.dump(body, f)
    return tmp


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(str(tmp_path_factory.mktemp("checkout4")))


def run(root: str, trace: bool = False, fault: str = "") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(bench=os.path.join(root, "bench"),
                        src=os.path.join(ROOT, "src"), root=root,
                        fault=fault, seed=SEED, trace=trace)
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_four_shard_cell_is_correct(checkout):
    out = run(checkout)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) >= {"iteration_ms", "plan_s", "setup_s"}
    assert all(c["value"] == 0 for c in out["checks"].values())


def test_one_shard_frozen_is_not_correct(checkout):
    out = run(checkout, fault="frozen_shard")
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"]["labels_differing"]["value"] > 0


def test_traced_run_on_the_cpu_reads_nothing_and_does_not_fail(checkout):
    out = run(checkout, trace=True)
    assert out["correct"] and out["attempted"] == 1
    assert "exchange_ms" not in out["metrics"]
    assert "shard_move_roofline" not in out["metrics"]


def test_readers_on_a_recorded_four_chip_trace():
    """The chip run printed shard_move_roofline 0.04187471855895081 for
    this solve (5 iterations, |V| = 2,097,152, |E| = 63,540,216).
    exchange_ms counts the sharded step's collectives alone: 0.2065 ms
    an iteration here, and not the 0.2364 the chip run printed, which
    also counted the all-reduce that gathers the labels back to vertex
    order once a solve."""
    events = trace.load(os.path.join(BENCH, "tests", "data",
                                     "trace_graph500_4chip_s21.json.gz"))
    (span,) = [e for e in events if e.name == "bench/solve"]
    summary = trace.Summary(events, span.start_ns, span.end_ns)
    assert len(summary.devices) == 4
    bench = harness.Bench()
    reading = types.SimpleNamespace(
        trace=summary, solves=[types.SimpleNamespace(iterations=5)],
        graph=types.SimpleNamespace(n_nodes=2097152, n_edges=63540216),
        peaks=bench.peaks("TPU v5 lite"))
    exchange = bench.metric_reader("exchange_ms")(reading)
    roofline = bench.metric_reader("shard_move_roofline")(reading)
    assert exchange == pytest.approx(0.20653994999999997, rel=1e-9)
    assert roofline == pytest.approx(0.04187471855895081, rel=1e-9)
    # the halo all-to-all, the hub all-gather and the moved-count psum
    # are each counted; the gather back to vertex order is a module of
    # its own, and its all-reduce is all that the step's windows leave out
    per_op = {kind: summary.op_s(f" {kind}\\(") for kind in
              ("all-to-all", "all-gather", "all-reduce")}
    assert all(v > 0 for v in per_op.values()), per_op
    gather = [m for m in summary.modules
              if trace.module_name(m.name) == "jit__labels_by_vertex"]
    assert len(gather) == 4
    in_gather = sum(o.dur_ns for o in summary.ops
                    if " all-reduce(" in o.name and any(
                        o.plane == m.plane
                        and m.start_ns <= o.start_ns < m.end_ns
                        for m in gather)) * 1e-9 / 4
    assert in_gather > 0
    assert 1e3 * (sum(per_op.values()) - in_gather) / 5 == \
        pytest.approx(exchange)
