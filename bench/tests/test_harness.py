"""The harness on the CPU: a cell, a mix and a metric added as new files
only are found by name; a sound run is correct; a run with the timed path
broken underneath is not. The look for a chip is skipped here (on the
CPU the Pallas kernels run in interpret mode); the command itself refuses
to run without a TPU."""
from __future__ import annotations

import importlib
import os
import subprocess
import sys
import time

import helpers
import jax.numpy as jnp
import pytest

from benchlib import harness

lpa_mod = importlib.import_module("repro.core.lpa")

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return harness.Bench(helpers.make_checkout(
        str(tmp_path_factory.mktemp("checkout"))))


def run(bench, trace=False):
    return harness.run("tiny.mg8s", SEED, 0.2, trace,
                       t_start=time.perf_counter(), bench=bench,
                       require_tpu=False)


def test_new_files_only_make_a_cell(checkout):
    out = run(checkout)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    # the metric added as a file is read; the cell reports its e2e metrics
    assert set(out["metrics"]) >= {"edges", "iteration_ms", "plan_s",
                                   "setup_s"}
    assert "modularity" not in out["metrics"]  # listed for kmer.mg8 only
    assert out["metrics"]["edges"]["unit"] == "slots"
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in out["checks"].values())


def test_traced_run_reads_per_layer_metrics(checkout):
    out = run(checkout, trace=True)
    assert out["correct"] and out["attempted"] == 1
    assert out["device"]["window_s"] > 0
    assert set(out["metrics"]) == {"slots"}  # per-layer, added as a file
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def unchanged(orig):
    def move(ws, labels, *a, **k):
        return labels, jnp.zeros(labels.shape, bool)
    return move


def half_left_out(orig):
    def move(ws, labels, *a, **k):
        new, _ = orig(ws, labels, *a, **k)
        keep = jnp.arange(labels.shape[0]) < labels.shape[0] // 2
        new = jnp.where(keep, new, labels)
        return new, new != labels
    return move


def answer_altered(orig):
    def move(ws, labels, *a, **k):
        new, _ = orig(ws, labels, *a, **k)
        new = new.at[0].set(labels.shape[0])
        return new, new != labels
    return move


@pytest.mark.parametrize("fault", [unchanged, half_left_out, answer_altered])
def test_broken_timed_path_is_not_correct(checkout, monkeypatch, fault):
    monkeypatch.setattr(lpa_mod, "lpa_move", fault(lpa_mod.lpa_move))
    out = run(checkout)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(helpers.BENCH, "run.py"),
                        "--workload", "graph500.mg8", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env,
                       cwd=helpers.ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
