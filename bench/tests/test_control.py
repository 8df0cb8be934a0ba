"""The reference against the program, and the controls against the
comparison, at sizes a test run holds. The runs at the cells' own sizes
are in PERF.md (``python3 bench/controls.py``)."""
from __future__ import annotations

import helpers
import numpy as np
import pytest

from benchlib import graphs, harness, reference

MIX = {"lpa_config": {"method": "mg", "k": 8, "chunk": 128, "rho": 8,
                      "tau": 0.05, "max_iters": 20}}
RMAT = {"family": "rmat", "params": {"scale": 9, "edge_factor": 16,
                                     "a": 0.57, "b": 0.19, "c": 0.19,
                                     "weighted": True},
        "base_seed": 1, "control": "bf16"}
KMER = {"family": "chain_kmer", "params": {"n_vertices": 60000,
                                           "branch_prob": 0.05},
        "base_seed": 1, "control": "k4"}


def as_solve(r):
    return harness.Solve(r.labels, r.iterations, r.changed_history)


@pytest.mark.parametrize("config", [RMAT, KMER], ids=["rmat", "kmer"])
@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_reference_matches_program(config, seed):
    import jax.numpy as jnp
    from repro.core import LPAConfig, lpa
    from repro.graphs.csr import CSRGraph
    g = graphs.generate(config, seed)
    if g.n_nodes > 4096:  # the program's jnp fold on the CPU stays small
        g = graphs.generate(dict(config, params=dict(config["params"],
                                                     n_vertices=4096)), seed)
    res = lpa(CSRGraph(jnp.asarray(g.offsets), jnp.asarray(g.indices),
                       jnp.asarray(g.weights), g.n_nodes, g.n_edges),
              LPAConfig(**MIX["lpa_config"], fold_backend="jnp"))
    prog = harness.Solve(np.asarray(res.labels), res.iterations,
                         list(res.changed_history))
    checks, failed = harness.compare([prog], harness.reference_solve(g, MIX))
    assert failed == 0, checks


@pytest.mark.parametrize("config", [RMAT, KMER], ids=["rmat", "kmer"])
@pytest.mark.parametrize("seed", [11, 2**31 + 5, 77])
def test_configured_control_is_rejected(config, seed):
    g = graphs.generate(config, seed)
    ref = harness.reference_solve(g, MIX)
    ctl = harness.reference_solve(g, MIX,
                                  **reference.CONTROLS[config["control"]])
    checks, failed = harness.compare([as_solve(ctl)], ref)
    assert failed == 1, checks


def test_configs_name_their_controls():
    for name in ("graph500", "kmer"):
        cfg = harness.Bench().config(name)
        assert cfg["control"] in reference.CONTROLS
        assert cfg["family"] == {"graph500": "rmat",
                                 "kmer": "chain_kmer"}[name]
    assert harness.Bench().config("graph500")["params"]["weighted"]


@pytest.mark.parametrize("config", [RMAT, KMER], ids=["rmat", "kmer"])
def test_k4_control_is_rejected(config):
    g = graphs.generate(config, 11)
    ref = harness.reference_solve(g, MIX)
    ctl = harness.reference_solve(g, MIX, **reference.CONTROLS["k4"])
    checks, failed = harness.compare([as_solve(ctl)], ref)
    assert failed == 1 and checks["moved_counts_differing"]["value"] > 0


def test_bf16_control_reads_as_float32_on_unit_weights():
    # every counter is a whole count of edges, which bfloat16 holds exactly:
    # the same labels and moves, so the unit-weight cell's control is k4
    g = graphs.generate(KMER, 11)
    ref = harness.reference_solve(g, MIX)
    ctl = harness.reference_solve(g, MIX, **reference.CONTROLS["bf16"])
    assert harness.compare([as_solve(ctl)], ref)[1] == 0
