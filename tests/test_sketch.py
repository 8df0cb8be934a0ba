"""Unit + hypothesis property tests for the weighted MG / BM sketch folds.

The theoretical contracts under test (paper §3.4/3.5 + Agarwal et al.):
  * MG guarantee: any label whose total weight exceeds W_total/(k+1) is
    present in the final sketch (heavy hitters are never evicted).
  * MG underestimation: the sketch weight of a label never exceeds its true
    total weight, and undercounts by at most W_total/(k+1).
  * BM majority: if one label holds a strict weighted majority, BM returns
    it (k=1 degenerate MG).
  * Mergeability: folding a stream in chunks and merging the partial
    sketches preserves the heavy-hitter guarantee with k slots.
"""
import numpy as np
import jax.numpy as jnp
import pytest
from _propcheck import given, settings, st

from repro.core.sketch import (bm_fold_tile, choose_from_candidates,
                               hash_mix, mg_fold_tile, run_mg_plan,
                               scatter_rows)
from repro.graphs.csr import build_fold_plan


# ---------------------------------------------------------------------------
# python oracle: the paper's Alg. 2 semantics, one row at a time. Callers
# pass float32 weights, so the oracle runs the folds' own float32
# arithmetic: a float64 oracle can see a tie (wk == w) that float32
# rounding does not, and keep a different candidate.
# ---------------------------------------------------------------------------

def mg_oracle(labels, weights, k):
    s_k = [-1] * k
    s_v = [0.0] * k
    for c, w in zip(labels, weights):
        if w <= 0 or c < 0:
            continue
        for s in range(k):
            if s_v[s] > 0 and s_k[s] == c:
                s_v[s] += w
                break
        else:
            for s in range(k):
                if s_v[s] <= 0:
                    s_k[s], s_v[s] = c, w
                    break
            else:
                s_v = [max(v - w, 0.0) for v in s_v]
    return s_k, s_v


def bm_oracle(labels, weights, init=-1):
    ck, wk = init, 0.0
    for c, w in zip(labels, weights):
        if w <= 0 or c < 0:
            continue
        if c == ck:
            wk += w
        elif wk > w:
            wk -= w
        else:
            ck, wk = c, w
    return ck, wk


# ---------------------------------------------------------------------------
# direct fold-vs-oracle agreement
# ---------------------------------------------------------------------------

row_strategy = st.lists(
    st.tuples(st.integers(min_value=0, max_value=9),
              st.floats(min_value=0.1, max_value=10.0, allow_nan=False)),
    min_size=1, max_size=48)


@settings(max_examples=60, deadline=None)
@given(row=row_strategy, k=st.sampled_from([1, 2, 4, 8]))
def test_mg_fold_matches_oracle(row, k):
    labels = np.array([c for c, _ in row], dtype=np.int32)[None]
    weights = np.array([w for _, w in row], dtype=np.float32)[None]
    s_k, s_v = mg_fold_tile(jnp.asarray(labels), jnp.asarray(weights), k)
    ok, ov = mg_oracle(labels[0], weights[0], k)
    got = {int(c): float(v) for c, v in zip(np.asarray(s_k)[0],
                                            np.asarray(s_v)[0]) if v > 0}
    want = {int(c): float(v) for c, v in zip(ok, ov) if v > 0}
    assert set(got) == set(want)
    for c in want:
        assert got[c] == pytest.approx(want[c], rel=1e-5, abs=1e-4)


@settings(max_examples=60, deadline=None)
@given(row=row_strategy)
def test_bm_fold_matches_oracle(row):
    labels = np.array([c for c, _ in row], dtype=np.int32)[None]
    weights = np.array([w for _, w in row], dtype=np.float32)[None]
    ck, wk = bm_fold_tile(jnp.asarray(labels), jnp.asarray(weights))
    oc, ow = bm_oracle(labels[0], weights[0])
    assert int(ck[0]) == oc
    assert float(wk[0]) == pytest.approx(ow, rel=1e-5, abs=1e-4)


def test_bm_fold_matches_oracle_at_a_float32_near_tie():
    """8.2825066 - 1 - 1 is one float32 ulp above 6.2825066, so the
    incumbent survives; in float64 the two tie and the new label wins."""
    labels = np.array([[1, 1, 0, 0, 0]], dtype=np.int32)
    weights = np.array([[1.0, 7.282506619268444, 1.0, 1.0,
                         6.282506619268444]], dtype=np.float32)
    ck, wk = bm_fold_tile(jnp.asarray(labels), jnp.asarray(weights))
    assert bm_oracle(labels[0], weights[0]) == (1, np.float32(wk[0]))
    assert int(ck[0]) == 1


# ---------------------------------------------------------------------------
# theoretical guarantees
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(labels=st.lists(st.integers(0, 9), min_size=1, max_size=64),
       k=st.sampled_from([2, 4, 8]))
def test_mg_heavy_hitter_guarantee_unit_weights(labels, k):
    """Classic MG guarantee — any label with count > n/(k+1) survives.

    NOTE this holds for UNIT weights only (the paper's experimental
    setting, §5.1.3: all edge weights are 1). The paper's weighted
    decrement rule (subtract the full incoming w from every slot and drop
    the incoming item) does NOT preserve the guarantee for arbitrary
    weights: hypothesis found [(0,1),(1,1),(2,2)] @ k=2 where label 2 holds
    half the total weight yet is evicted. Documented in DESIGN.md §8; the
    guarantee LPA actually relies on (heavy labels arrive as many unit
    edges) is the one tested here.
    """
    labels = np.asarray(labels, dtype=np.int32)
    n = len(labels)
    weights = np.ones(n, dtype=np.float32)
    true = {c: int((labels == c).sum()) for c in set(labels.tolist())}
    s_k, s_v = mg_fold_tile(jnp.asarray(labels[None]),
                            jnp.asarray(weights[None]), k)
    present = {int(c) for c, v in zip(np.asarray(s_k)[0], np.asarray(s_v)[0])
               if v > 0}
    for c, cnt in true.items():
        if cnt > n / (k + 1):
            assert c in present, (c, cnt, n, present)


@settings(max_examples=60, deadline=None)
@given(row=row_strategy, k=st.sampled_from([2, 4, 8]))
def test_mg_weight_never_overestimates(row, k):
    """Sketch weight of a label never exceeds its true total weight (holds
    for arbitrary weights — decrements only reduce)."""
    labels = np.array([c for c, _ in row], dtype=np.int32)
    weights = np.array([w for _, w in row], dtype=np.float64)
    true = {}
    for c, w in zip(labels, weights):
        true[c] = true.get(c, 0.0) + w
    s_k, s_v = mg_fold_tile(jnp.asarray(labels[None]),
                            jnp.asarray(weights[None].astype(np.float32)), k)
    for c, v in zip(np.asarray(s_k)[0], np.asarray(s_v)[0]):
        if v <= 0:
            continue
        assert v <= true[int(c)] + 1e-3


@settings(max_examples=60, deadline=None)
@given(labels=st.lists(st.integers(0, 9), min_size=1, max_size=64),
       k=st.sampled_from([2, 4, 8]))
def test_mg_undercount_bounded_unit_weights(labels, k):
    """Unit weights: undercount is at most n/(k+1) (classic MG bound)."""
    labels = np.asarray(labels, dtype=np.int32)
    n = len(labels)
    true = {c: int((labels == c).sum()) for c in set(labels.tolist())}
    s_k, s_v = mg_fold_tile(jnp.asarray(labels[None]),
                            jnp.asarray(np.ones((1, n), np.float32)), k)
    for c, v in zip(np.asarray(s_k)[0], np.asarray(s_v)[0]):
        if v <= 0:
            continue
        assert v >= true[int(c)] - n / (k + 1) - 1e-3


@settings(max_examples=60, deadline=None)
@given(labels=st.lists(st.integers(0, 5), min_size=2, max_size=48))
def test_bm_majority_guarantee_unit_weights(labels):
    """A strict-majority label is always BM's answer — UNIT weights.

    Like the MG rule (see above), the paper's weighted BM does NOT carry
    the classic guarantee for arbitrary weights: Alg. 3's replace branch
    sets w# to the FULL incoming w (not w − w#), so an exact-tie mismatch
    hands the rival the incumbent's destroyed votes for free — hypothesis
    found [(1,2.0),(0,2.0),(1,1.0)] where majority label 1 loses. With
    unit weights the rule is the classic MJRTY vote (replacement transfers
    exactly one vote) and the guarantee holds; the paper evaluates unit
    weights only (§5.1.3). Documented in DESIGN.md §8.4.
    """
    labels = np.asarray(labels, dtype=np.int32)
    n = len(labels)
    counts = {c: int((labels == c).sum()) for c in set(labels.tolist())}
    best_c, best_n = max(counts.items(), key=lambda cv: cv[1])
    if best_n <= n / 2:
        return  # no strict majority -> no guarantee
    ck, _ = bm_fold_tile(jnp.asarray(labels[None]),
                         jnp.asarray(np.ones((1, n), np.float32)))
    assert int(ck[0]) == best_c


def test_bm_weighted_majority_counterexample_documented():
    """The paper-faithful weighted BM drops a strict-majority label on an
    exact-tie replace — the documented deviation (DESIGN.md §8.4)."""
    labels = jnp.asarray([[1, 0, 1]], jnp.int32)
    weights = jnp.asarray([[2.0, 2.0, 1.0]], jnp.float32)
    ck, _ = bm_fold_tile(labels, weights)
    assert int(ck[0]) == 0  # label 1 holds 3/5 of the weight yet loses


# ---------------------------------------------------------------------------
# multi-round plan: chunking + merge preserve heavy hitters
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(deg=st.integers(min_value=1, max_value=700),
       heavy_frac=st.floats(min_value=0.45, max_value=0.9),
       seed=st.integers(0, 1000))
def test_mg_plan_merge_keeps_heavy_label(deg, heavy_frac, seed):
    """One vertex with ``deg`` neighbors, one label holding > heavy_frac of
    the weight: the multi-round (chunked + merged) MG fold must keep it and
    rank it first."""
    k, chunk = 8, 64
    rng = np.random.default_rng(seed)
    n_heavy = max(int(deg * heavy_frac), 1)
    labels = np.concatenate([np.zeros(n_heavy, np.int32),
                             rng.integers(1, 1000, deg - n_heavy)])
    rng.shuffle(labels)
    weights = np.ones(deg, dtype=np.float32)
    if n_heavy <= deg / 2:
        return  # only test strict majority (guaranteed survivable)
    plan = build_fold_plan(np.array([deg]), k=k, chunk=chunk)
    s_k, s_v = run_mg_plan(plan, jnp.asarray(labels.astype(np.int32)),
                           jnp.asarray(weights))
    cand_c, cand_w = scatter_rows(plan, s_k, s_v)
    row_c, row_w = np.asarray(cand_c)[0], np.asarray(cand_w)[0]
    assert 0 in row_c[row_w > 0]
    assert row_c[np.argmax(row_w)] == 0


def test_mg_fold_empty_rows():
    labels = jnp.full((4, 8), -1, jnp.int32)
    weights = jnp.zeros((4, 8), jnp.float32)
    s_k, s_v = mg_fold_tile(labels, weights, 8)
    assert (np.asarray(s_v) == 0).all()


def test_bm_fold_replaces_on_tie():
    """Paper Alg. 3 l.17: 'else if w# > w' is a STRICT compare, so an
    equal-weight rival replaces the candidate — [3,7,3,7] ends on 7."""
    labels = jnp.asarray([[3, 7, 3, 7]], jnp.int32)
    weights = jnp.ones((1, 4), jnp.float32)
    ck, wk = bm_fold_tile(labels, weights, jnp.asarray([3], jnp.int32))
    assert int(ck[0]) == 7
    assert float(wk[0]) == 1.0


def test_bm_plan_merge_prefers_incumbent():
    """run_bm_plan's cross-partial merge (paper §4.7 pair-max reduce) keeps
    the incumbent when it ties the best rival partial."""
    from repro.core.sketch import run_bm_plan
    # one vertex, degree 2*chunk so two partial folds are produced
    chunk = 16
    deg = 2 * chunk
    plan = build_fold_plan(np.asarray([deg]), k=1, chunk=chunk)
    # chunk A all label 5, chunk B all label 9 -> partials tie at weight 16
    labels = np.concatenate([np.full(chunk, 5), np.full(chunk, 9)])
    weights = np.ones(deg, np.float32)
    cur = jnp.asarray([5], jnp.int32)  # incumbent = 5
    best, w = run_bm_plan(plan, jnp.asarray(labels.astype(np.int32)),
                          jnp.asarray(weights), cur)
    assert int(best[0]) == 5
    # incumbent 7 (absent from stream): rivals tie, smaller label wins
    best2, _ = run_bm_plan(plan, jnp.asarray(labels.astype(np.int32)),
                           jnp.asarray(weights), jnp.asarray([7], jnp.int32))
    assert int(best2[0]) in (5, 9)


# ---------------------------------------------------------------------------
# move selection
# ---------------------------------------------------------------------------

def test_choose_prefers_max_weight():
    cand_c = jnp.asarray([[5, 9, -1]], jnp.int32)
    cand_w = jnp.asarray([[2.0, 3.0, 0.0]], jnp.float32)
    labels = jnp.asarray([7], jnp.int32)
    out = choose_from_candidates(cand_c, cand_w, labels, jnp.int32(1))
    assert int(out[0]) == 9


def test_choose_keeps_label_when_no_candidates():
    cand_c = jnp.full((3, 4), -1, jnp.int32)
    cand_w = jnp.zeros((3, 4), jnp.float32)
    labels = jnp.asarray([4, 5, 6], jnp.int32)
    out = choose_from_candidates(cand_c, cand_w, labels, jnp.int32(1))
    assert (np.asarray(out) == [4, 5, 6]).all()


def test_choose_tie_break_deterministic_and_seed_dependent():
    cand_c = jnp.asarray([[2, 11, -1]], jnp.int32)
    cand_w = jnp.asarray([[1.0, 1.0, 0.0]], jnp.float32)
    labels = jnp.asarray([99], jnp.int32)
    picks = {int(choose_from_candidates(cand_c, cand_w, labels,
                                        jnp.int32(s))[0])
             for s in range(16)}
    assert picks <= {2, 11}
    assert len(picks) == 2, "hash tie-break should vary across seeds"
    a = choose_from_candidates(cand_c, cand_w, labels, jnp.int32(3))
    b = choose_from_candidates(cand_c, cand_w, labels, jnp.int32(3))
    assert int(a[0]) == int(b[0])


def test_hash_mix_is_deterministic_and_spreads():
    x = jnp.arange(1024, dtype=jnp.int32)
    h1 = hash_mix(x, jnp.int32(5))
    h2 = hash_mix(x, jnp.int32(5))
    h3 = hash_mix(x, jnp.int32(6))
    assert (np.asarray(h1) == np.asarray(h2)).all()
    assert (np.asarray(h1) != np.asarray(h3)).mean() > 0.99
    # no catastrophic collisions on small ints
    assert len(np.unique(np.asarray(h1))) > 1000


@settings(max_examples=60, deadline=None)
@given(row=row_strategy, k=st.sampled_from([2, 4, 8]))
def test_exact_weighted_mg_guarantee(row, k):
    """The exact-weighted variant restores the MG guarantee for ARBITRARY
    positive weights — the case the paper's rule fails (DESIGN.md §8.4)."""
    from repro.core.sketch import mg_fold_tile_exact_weighted
    labels = np.array([c for c, _ in row], dtype=np.int32)
    weights = np.array([w for _, w in row], dtype=np.float64)
    total = weights.sum()
    true = {}
    for c, w in zip(labels, weights):
        true[c] = true.get(c, 0.0) + w
    s_k, s_v = mg_fold_tile_exact_weighted(
        jnp.asarray(labels[None]),
        jnp.asarray(weights[None].astype(np.float32)), k)
    present = {int(c) for c, v in zip(np.asarray(s_k)[0],
                                      np.asarray(s_v)[0]) if v > 0}
    for c, w in true.items():
        if w > total / (k + 1) + 1e-3:
            assert c in present, (c, w, total, present)


def test_exact_weighted_mg_fixes_paper_counterexample():
    """[(0,1),(1,1),(2,2)] @ k=2: paper rule evicts label 2 (half the
    weight); the exact variant keeps it."""
    from repro.core.sketch import mg_fold_tile_exact_weighted
    labels = jnp.asarray([[0, 1, 2]], jnp.int32)
    weights = jnp.asarray([[1.0, 1.0, 2.0]], jnp.float32)
    s_k_p, s_v_p = mg_fold_tile(labels, weights, 2)
    paper_kept = {int(c) for c, v in zip(np.asarray(s_k_p)[0],
                                         np.asarray(s_v_p)[0]) if v > 0}
    assert 2 not in paper_kept  # the documented failure
    s_k_e, s_v_e = mg_fold_tile_exact_weighted(labels, weights, 2)
    exact_kept = {int(c) for c, v in zip(np.asarray(s_k_e)[0],
                                         np.asarray(s_v_e)[0]) if v > 0}
    assert 2 in exact_kept


def test_exact_weighted_equals_paper_on_unit_weights():
    """With unit weights both variants are classic MG — identical output."""
    from repro.core.sketch import mg_fold_tile_exact_weighted
    rng = np.random.default_rng(7)
    labels = jnp.asarray(rng.integers(0, 12, (16, 48)).astype(np.int32))
    weights = jnp.ones((16, 48), jnp.float32)
    a_k, a_v = mg_fold_tile(labels, weights, 8)
    b_k, b_v = mg_fold_tile_exact_weighted(labels, weights, 8)
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(b_k))
    np.testing.assert_array_equal(np.asarray(a_v), np.asarray(b_v))


def test_lpa_exact_weighted_variant_on_weighted_graph():
    """On a weighted graph the exact-weighted sketch matches the exact
    method's choice where the paper rule can drop heavy edges."""
    from repro.core.lpa import LPAConfig, lpa
    from repro.graphs.csr import build_csr
    edges = np.asarray([[0, 1], [0, 2], [0, 3], [1, 2], [2, 3], [1, 3],
                        [0, 4], [4, 5], [5, 6], [4, 6]])
    w = np.asarray([1, 1, 1, 1, 1, 1, 10, 10, 10, 10], np.float32)
    g = build_csr(edges, 7, weights=w)
    res = lpa(g, LPAConfig(method="mg", mg_variant="exact_weighted", rho=2))
    assert int(res.labels[0]) == int(res.labels[4])
