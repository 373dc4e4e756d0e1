"""A sparse document-term matrix shaped like LIBSVM ``rcv1.binary``.

Vectorised NumPy, on the host, from the seed: lognormal words per document
with mean ``density * n_features``, a Zipf(``zipf``) column popularity under
a random permutation, a share ``topic_share`` of each document's words from
a ``vocab``-word vocabulary of its class (taken from the rare end of the
popularity order) and positive weights uniform in [0.1, 1.1).  Balanced
labels in {-1, +1}.  The smoke run's ``_rcv1_like``; its last step, which
scales each row to unit norm as rcv1's are, only where the configuration
sets ``unit_norm_rows``.  With unit-norm rows every k-means++ centre has
``|c|^2 = 1`` to within one float32 ulp, so the first Lloyd assignment of
each row that shares no word with two centres is decided by the rounding
of that sum, and no two correct implementations agree on the clustering
that follows.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sp

# the sizes a CPU test holds: some thirty words a document; with rcv1's own
# seventy-six, near-equal distances (whose order rounding may swap) are
# rare; with three they are common and the Lloyd steps part ways
TINY = dict(n_rows=3000, n_features=1000, density=0.03, block_rows=512,
            block_nse=20000, vocab=20)


def make(cell):
    """``(mat, y)``: a float32 CSR matrix with sorted column indices and no
    duplicates, and the labels."""
    c = cell.config
    rng = cell.rng("corpus")
    n, m = c["n_rows"], c["n_features"]
    vocab = c["vocab"]
    want = c["density"] * m
    y = rng.permutation(np.arange(n) % 2) * 2 - 1
    per_row = np.maximum(1, rng.lognormal(np.log(want) - 0.18,
                                          c["words_sigma"], n)
                         .astype(np.int64))
    pop = 1.0 / np.arange(1, m + 1) ** c["zipf"]
    cdf = np.cumsum(pop / pop.sum())
    order = rng.permutation(m)
    cols = order[np.minimum(np.searchsorted(cdf, rng.random(per_row.sum())),
                            m - 1)]
    doc = np.repeat(np.arange(n, dtype=np.int64), per_row)
    topical = rng.random(doc.size) < c["topic_share"]
    words = order[-2 * vocab:].reshape(2, vocab)
    cols = np.where(topical, words[(y[doc] + 1) // 2,
                                   rng.integers(0, vocab, doc.size)], cols)
    del topical
    keys = np.unique(doc * m + cols)
    del doc, cols
    rows = keys // m
    vals = (rng.random(keys.size) + 0.1).astype(np.float32)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n), out=indptr[1:])
    mat = sp.csr_matrix((vals, (keys % m).astype(np.int32), indptr),
                        shape=(n, m))
    del keys, rows
    mat.has_sorted_indices = True
    if c["unit_norm_rows"]:
        norms = np.sqrt(np.add.reduceat(mat.data.astype(np.float64) ** 2,
                                        mat.indptr[:-1]))
        mat.data /= np.repeat(norms, np.diff(mat.indptr)).astype(np.float32)
    return mat, y
