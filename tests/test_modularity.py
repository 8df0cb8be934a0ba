"""Modularity (paper Eq. 1) and NMI metric tests."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.modularity import community_sizes, modularity, nmi
from repro.graphs.csr import build_csr
from repro.graphs.generators import ring_of_cliques


def test_modularity_analytic_two_triangles():
    """Two triangles joined by one edge; the 2-community split has
    Q = sum_c [sigma_c/2m - (Sigma_c/2m)^2] = 2*(3/7 - (7/14)^2) = 5/14."""
    edges = np.asarray([[0, 1], [1, 2], [0, 2],
                        [3, 4], [4, 5], [3, 5],
                        [2, 3]])
    g = build_csr(edges, 6)
    labels = jnp.asarray([0, 0, 0, 1, 1, 1], jnp.int32)
    np.testing.assert_allclose(float(modularity(g, labels)), 5.0 / 14.0,
                               rtol=1e-6)


def test_modularity_single_community_is_zero():
    edges = np.asarray([[0, 1], [1, 2], [0, 2]])
    g = build_csr(edges, 3)
    q = float(modularity(g, jnp.zeros(3, jnp.int32)))
    np.testing.assert_allclose(q, 0.0, atol=1e-6)


def test_modularity_single_community_is_zero_past_float32_sums():
    """One community whose weight sums pass 2^24: float32 accumulation
    drops the unit edges next to the heavy one, and Q drifts off 0."""
    leaves = np.arange(2, 1002)
    edges = np.concatenate([[[0, 1]], np.stack([np.zeros_like(leaves),
                                                leaves], axis=1)])
    weights = np.concatenate([[2.0 ** 24], np.ones(len(leaves))])
    g = build_csr(edges, 1002, weights=weights.astype(np.float32))
    q = modularity(g, jnp.zeros(1002, jnp.int32))
    assert q == 0.0


def test_modularity_bounds():
    g, truth = ring_of_cliques(8, 6)
    for labels in (jnp.asarray(truth, jnp.int32),
                   jnp.arange(g.n_nodes, dtype=jnp.int32),
                   jnp.zeros(g.n_nodes, jnp.int32)):
        q = float(modularity(g, labels))
        assert -0.5 - 1e-6 <= q <= 1.0 + 1e-6


def test_modularity_planted_beats_random():
    g, truth = ring_of_cliques(8, 6)
    rng = np.random.default_rng(0)
    q_truth = float(modularity(g, jnp.asarray(truth, jnp.int32)))
    q_rand = float(modularity(g, jnp.asarray(
        rng.integers(0, 8, g.n_nodes), jnp.int32)))
    assert q_truth > q_rand + 0.3


def test_modularity_respects_weights():
    # heavy intra edges raise Q for the matching partition
    edges = np.asarray([[0, 1], [2, 3], [1, 2]])
    w_flat = np.asarray([1.0, 1.0, 1.0], np.float32)
    w_heavy = np.asarray([10.0, 10.0, 1.0], np.float32)
    labels = jnp.asarray([0, 0, 1, 1], jnp.int32)
    g1 = build_csr(edges, 4, weights=w_flat)
    g2 = build_csr(edges, 4, weights=w_heavy)
    assert float(modularity(g2, labels)) > float(modularity(g1, labels))


def test_nmi_properties():
    a = np.asarray([0, 0, 1, 1, 2, 2])
    assert nmi(a, a) == pytest.approx(1.0)
    # label permutation invariant
    assert nmi(a, (a + 1) % 3) == pytest.approx(1.0)
    # independent labels -> low NMI
    b = np.asarray([0, 1, 0, 1, 0, 1])
    assert nmi(a, b) < 0.5
    assert 0.0 <= nmi(a, b) <= 1.0


def test_community_sizes_sorted():
    sizes = community_sizes(np.asarray([0, 0, 0, 1, 2, 2]))
    assert sizes.tolist() == [3, 2, 1]
