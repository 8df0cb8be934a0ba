"""Graph inputs of a cell: one graph per configuration, renumbered by the seed.

A configuration file names a generator family (``bench/families/<family>.py``,
found by name), its parameters and a ``base_seed``. The family draws the
configuration's one edge list, with a weight per drawn edge, from
``base_seed``; the run's seed permutes the vertex ids. So every seed gives
the same graph in another order, as the data set is one graph: the same
degrees and plan sizes, with different labels, hash ties and label
dynamics. The edge list becomes a symmetric weighted CSR graph by the
rules the program's ``build_csr`` documents: self-loops dropped, both
directions stored, neighbours sorted by id, the drawn edges joining one
pair merged into one edge whose weight is the sum of theirs (their count,
where every drawn edge weighs 1).

Generation is the benchmark's own cost. A child process draws the merged,
unpermuted edges once per configuration and size into
``bench/.cache/graphs``; a run reads them from there, so its process is in
the same state whether the cache had them or not, and applies its seed's
permutation itself. Plans and labels are never cached.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import multiprocessing
import os
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class HostGraph:
    offsets: np.ndarray  # [N+1] int32
    indices: np.ndarray  # [M] int32, both directions stored
    weights: np.ndarray  # [M] float32, summed weights of the drawn edges

    @property
    def n_nodes(self) -> int:
        return len(self.offsets) - 1

    @property
    def n_edges(self) -> int:
        return len(self.indices)

    def sources(self) -> np.ndarray:
        return np.repeat(np.arange(self.n_nodes, dtype=np.int32),
                         np.diff(self.offsets))


@dataclasses.dataclass
class BaseGraph:
    """The merged undirected edges before any permutation."""

    pairs: np.ndarray    # [P, 2] int32, u < v, each pair once
    weights: np.ndarray  # [P] float32
    n: int


def merge(edges: np.ndarray, n: int, weights=None) -> BaseGraph:
    """Drops self-loops and merges the drawn edges joining one pair."""
    keep = edges[:, 0] != edges[:, 1]
    e = np.sort(edges[keep], axis=1)  # undirected: (min, max)
    w = (np.ones(len(e)) if weights is None
         else np.asarray(weights, dtype=np.float64)[keep])
    key = e[:, 0].astype(np.int64) * n + e[:, 1]
    order = np.argsort(key, kind="stable")
    key = key[order]
    first = np.nonzero(np.concatenate([[True], key[1:] != key[:-1]]))[0]
    summed = np.add.reduceat(w[order], first) if len(first) else w[:0]
    key = key[first]
    pairs = np.stack([key // n, key % n], axis=1).astype(np.int32)
    return BaseGraph(pairs=pairs, weights=summed.astype(np.float32), n=n)


def to_csr(base: BaseGraph, perm=None) -> HostGraph:
    """Both directions of every pair, the ids renumbered by ``perm``."""
    n = base.n
    p = base.pairs if perm is None else perm[base.pairs]
    src = np.concatenate([p[:, 0], p[:, 1]]).astype(np.int64)
    dst = np.concatenate([p[:, 1], p[:, 0]]).astype(np.int64)
    order = np.argsort(src * n + dst)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=offsets[1:])
    return HostGraph(offsets=offsets.astype(np.int32),
                     indices=dst[order].astype(np.int32),
                     weights=np.concatenate([base.weights,
                                             base.weights])[order])


def load_family(family: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "families", f"{family}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no generator family {family!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"family_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate_base(config: dict, bench_dir: str = BENCH_DIR) -> BaseGraph:
    family = load_family(config["family"], bench_dir)
    edges, n, weights = family.generate(
        config["params"], np.random.default_rng(config["base_seed"]))
    return merge(edges, n, weights)


def permutation(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).permutation(n)


def generate(config: dict, seed: int, bench_dir: str = BENCH_DIR
             ) -> HostGraph:
    base = generate_base(config, bench_dir)
    return to_csr(base, permutation(base.n, seed))


def cache_path(config: dict, bench_dir: str = BENCH_DIR) -> str:
    key = json.dumps([config["family"], config["params"],
                      config["base_seed"]], sort_keys=True)
    digest = hashlib.sha256(key.encode()).hexdigest()[:16]
    return os.path.join(bench_dir, ".cache", "graphs",
                        f"{config['family']}-{digest}.npz")


def write(config: dict, bench_dir: str, path: str) -> None:
    b = generate_base(config, bench_dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.part.npz"
    np.savez(tmp, pairs=b.pairs, weights=b.weights, n=b.n)
    os.replace(tmp, path)


def load_or_generate(config: dict, seed: int, bench_dir: str = BENCH_DIR
                     ) -> tuple[HostGraph, float]:
    """Returns (the seed's graph, seconds spent generating the
    configuration's edges; 0 when the cache had them)."""
    path = cache_path(config, bench_dir)
    gen_s = 0.0
    if not os.path.isfile(path):
        t0 = time.perf_counter()
        child = multiprocessing.get_context("spawn").Process(
            target=write, args=(config, bench_dir, path))
        child.start()
        child.join()
        if child.exitcode != 0:
            raise RuntimeError(f"generating {path} failed "
                               f"(exit code {child.exitcode})")
        gen_s = time.perf_counter() - t0
    with np.load(path) as z:
        base = BaseGraph(z["pairs"], z["weights"], int(z["n"]))
    return to_csr(base, permutation(base.n, seed)), gen_s
