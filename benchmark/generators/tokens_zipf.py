"""One flat token file, ids drawn from a Zipf law over the vocabulary."""

import os

import numpy as np


def _rng(seed, stream):
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def generate(out_dir, seed, sequences, seq_len, vocab_size, exponent=1.1,
             dtype="uint16"):
    """Writes ``tokens.bin``; returns the product's data origin for it."""
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** -float(exponent)
    cdf = np.cumsum(p / p.sum())
    u = _rng(seed, 0).random(sequences * seq_len)
    ids = np.minimum(np.searchsorted(cdf, u), vocab_size - 1)
    # rank r is not token r: a fixed permutation from the seed spreads the
    # frequent ids over the embedding table.
    perm = _rng(seed, 1).permutation(vocab_size)
    path = os.path.join(out_dir, "tokens.bin")
    perm[ids].astype(np.dtype(dtype)).tofile(path)
    return "tokens:%s:%d:%s" % (path, seq_len, dtype)
