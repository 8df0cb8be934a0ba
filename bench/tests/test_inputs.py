"""Graph generation from the seed, and the roofline's byte count."""
from __future__ import annotations

import importlib.util
import os

import helpers
import numpy as np
import pytest

from benchlib import graphs
from benchlib.harness import Bench

BIG_SEED = 2**31 + 12345


def configs():
    bench = Bench()
    return {w["config"]: bench.config(w["config"])
            for w in bench.spec["workloads"]}


def shrunk(config):
    """The configuration's family and shapes at a size a test can hold."""
    p = dict(config["params"])
    if "scale" in p:
        p["scale"] = 9
    if "n_vertices" in p:
        p["n_vertices"] = 3000
    return dict(config, params=p)


@pytest.mark.parametrize("name", sorted(configs()))
def test_same_seed_same_input(name):
    cfg = shrunk(configs()[name])
    a, b = graphs.generate(cfg, BIG_SEED), graphs.generate(cfg, BIG_SEED)
    for x, y in zip((a.offsets, a.indices, a.weights),
                    (b.offsets, b.indices, b.weights)):
        np.testing.assert_array_equal(x, y)
    # another seed: the same graph with other vertex ids
    c = graphs.generate(cfg, BIG_SEED + 1)
    assert not np.array_equal(a.indices, c.indices)
    np.testing.assert_array_equal(np.sort(np.diff(a.offsets)),
                                  np.sort(np.diff(c.offsets)))
    np.testing.assert_array_equal(np.sort(a.weights), np.sort(c.weights))


@pytest.mark.parametrize("name", sorted(configs()))
def test_csr_is_symmetric_sorted_and_weighted_by_multiplicity(name):
    cfg = shrunk(configs()[name])
    family = graphs.load_family(cfg["family"])
    edges, n, w = family.generate(cfg["params"],
                                  np.random.default_rng(cfg["base_seed"]))
    w = np.ones(len(edges)) if w is None else w
    perm = np.random.default_rng(5).permutation(n)
    edges = perm[edges]
    g = graphs.generate(cfg, 5)
    src = g.sources()
    assert np.all(src != g.indices)
    key = src.astype(np.int64) * n + g.indices
    assert np.all(np.diff(key) > 0)  # sorted by (source, neighbour), unique
    # symmetric with equal weights, bit for bit
    back = dict(zip(key.tolist(), g.weights.tolist()))
    rkey = g.indices.astype(np.int64) * n + src
    assert [back[k] for k in rkey.tolist()] == g.weights.tolist()
    # weight = summed weight of the drawn edges joining the pair, either
    # direction (their number, where each weighs 1)
    keep = edges[:, 0] != edges[:, 1]
    e, w = edges[keep], w[keep]
    both = np.concatenate([e, e[:, ::-1]])
    pk = both[:, 0] * n + both[:, 1]
    pairs, inv = np.unique(pk, return_inverse=True)
    np.testing.assert_array_equal(pairs, key)
    np.testing.assert_allclose(g.weights, np.bincount(
        inv, weights=np.concatenate([w, w])), rtol=1e-6)
    assert (g.weights.dtype == np.float32 and np.all(g.weights > 0))
    if cfg["params"].get("weighted"):
        assert np.any(g.weights != np.round(g.weights))
    else:
        assert np.all(g.weights == np.round(g.weights))


def test_graph_cache_round_trip(tmp_path):
    cfg = shrunk(configs()["kmer"])
    bench_dir = os.path.join(helpers.make_checkout(str(tmp_path)), "bench")
    g, gen_s = graphs.load_or_generate(cfg, BIG_SEED, bench_dir)
    assert gen_s > 0
    # one file per configuration and size; any seed renumbers it
    h, gen_s = graphs.load_or_generate(cfg, BIG_SEED, bench_dir)
    assert gen_s == 0
    for x, y in ((g.offsets, h.offsets), (g.indices, h.indices),
                 (g.weights, h.weights)):
        np.testing.assert_array_equal(x, y)
    f = graphs.generate(cfg, BIG_SEED)
    np.testing.assert_array_equal(f.indices, h.indices)
    o, gen_s = graphs.load_or_generate(cfg, BIG_SEED + 1, bench_dir)
    assert gen_s == 0 and not np.array_equal(o.indices, h.indices)
    other = dict(cfg, params=dict(cfg["params"], n_vertices=3001))
    assert graphs.cache_path(other, bench_dir) != \
        graphs.cache_path(cfg, bench_dir)


def load_reader(name):
    path = os.path.join(helpers.BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_roofline_bytes_come_from_the_graph():
    roof = load_reader("move_roofline")
    # a triangle: 3 vertices, 6 directed slots
    assert roof.min_bytes(3, 6) == 12 * 6 + 8 * 3
    # graph500 scale 18 as measured: 262,144 vertices, 7,610,366 slots
    assert roof.min_bytes(262144, 7610366) == 93_421_544


def test_roofline_share_arithmetic():
    roof = load_reader("move_roofline")

    class Trace:
        def module_s_with(self, _):
            return 2.0  # two iterations of 1 s each

    class G:
        n_nodes, n_edges = 1000, 50_000

    class S:
        iterations = 2

    class R:
        trace, graph, solves = Trace(), G(), [S()]
        peaks = {"hbm_bytes_per_s": 819e9}

    least = (12 * 50_000 + 8 * 1000) / 819e9
    assert roof.read(R()) == pytest.approx(100 * least / 1.0)
