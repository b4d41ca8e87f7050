"""The benchmark's own seeded graph and feature generator.

A copy of ``repro.graphs.synth.power_law_adjacency`` and
``sparse_features`` (the generator the repository's Table-I datasets come
from), kept here so that no later change to the program can change the
benchmark's inputs. Everything is NumPy; the program receives the result
only as ``repro.core.csc.coo_from_arrays`` input.

Row degrees follow ``deg(rank) ∝ rank^-alpha`` (shuffled over rows),
columns are 60% uniform / 25% Zipf hubs / 15% local window, self loops are
added, duplicates removed, and the values are the symmetric normalization
``D^-1/2 (A+I) D^-1/2`` on total degree.
"""
from __future__ import annotations

import numpy as np


def _zipf_degrees(n, target_nnz, alpha, rng, max_degree):
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = ranks ** (-alpha)
    w /= w.sum()
    deg = np.maximum(1, np.round(w * target_nnz)).astype(np.int64)
    cap = n // 2 if max_degree is None else min(n // 2, max_degree)
    deg = np.minimum(deg, cap)
    rng.shuffle(deg)
    return deg


def power_law_adjacency(num_nodes, density, alpha, seed=0, max_degree=None):
    """``(rows, cols, vals)`` of a normalized power-law adjacency with self
    loops, row-major sorted: int64, int64, float32."""
    rng = np.random.default_rng(seed)
    target = max(num_nodes, int(density * num_nodes * num_nodes))
    deg = _zipf_degrees(num_nodes, target, alpha, rng, max_degree)
    rows = np.repeat(np.arange(num_nodes, dtype=np.int64), deg)
    m = rows.shape[0]

    u = rng.random(m)
    cols = np.empty(m, np.int64)
    uni = u < 0.60
    hub = (u >= 0.60) & (u < 0.85)
    loc = u >= 0.85
    cols[uni] = rng.integers(0, num_nodes, int(uni.sum()))
    ranks = np.arange(1, num_nodes + 1, dtype=np.float64)
    pw = ranks ** (-max(alpha, 0.8))
    cdf = np.cumsum(pw / pw.sum())
    perm = rng.permutation(num_nodes)
    cols[hub] = perm[np.searchsorted(cdf, rng.random(int(hub.sum())))]
    cols[loc] = np.clip(
        rows[loc] + rng.integers(-64, 65, int(loc.sum())), 0, num_nodes - 1
    )

    rows = np.concatenate([rows, np.arange(num_nodes, dtype=np.int64)])
    cols = np.concatenate([cols, np.arange(num_nodes, dtype=np.int64)])
    key = np.unique(rows * num_nodes + cols)
    rows = key // num_nodes
    cols = key % num_nodes

    degree = np.bincount(rows, minlength=num_nodes).astype(np.float64) + np.bincount(
        cols, minlength=num_nodes
    )
    dinv = 1.0 / np.sqrt(np.maximum(degree, 1.0))
    vals = (dinv[rows] * dinv[cols]).astype(np.float32)
    return rows, cols, vals


def sparse_features(num_nodes, num_features, density, seed=0):
    """Row-normalized sparse features stored dense (float32), every row
    with at least one non-zero."""
    rng = np.random.default_rng(seed + 1)
    x = np.zeros((num_nodes, num_features), np.float32)
    nnz = int(density * num_nodes * num_features)
    r = rng.integers(0, num_nodes, nnz)
    c = rng.integers(0, num_features, nnz)
    x[r, c] = rng.random(nnz).astype(np.float32) + 0.1
    x[np.arange(num_nodes), rng.integers(0, num_features, num_nodes)] += 0.5
    x /= x.sum(axis=1, keepdims=True)
    return x


def request_variants(base, count, drop, rng):
    """``count`` distinct feature matrices: ``base`` with a random ``drop``
    share of its non-zero entries zeroed in each."""
    nz = np.flatnonzero(base)
    out = []
    for _ in range(count):
        x = base.copy()
        x.reshape(-1)[nz[rng.random(nz.shape[0], dtype=np.float32) < drop]] = 0.0
        out.append(x)
    return out
