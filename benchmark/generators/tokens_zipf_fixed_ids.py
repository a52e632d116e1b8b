"""One flat token file, ids drawn from a Zipf law over the vocabulary,
the frequent ids the same in every file.

``tokens_zipf`` draws the permutation that says which id has which rank
from the seed, so every seed makes another set of ids the frequent ones.
To a model that is a different language every run: which embedding rows
a step reads most, and so, behind a router, which experts the frequent
tokens choose.  Where a chip holds a share of the experts that moves the
rows it multiplies by a tenth and more from seed to seed (one id is 15%
of the tokens at exponent 1.1 and routes as one block), and the job's
rate with them: PERF.md section 6, PR 33.  A tokenizer's frequent ids
are a property of the tokenizer, not of the run: here the permutation
comes from a constant, and the seed draws the sample alone.  The same
seed gives the same bytes; another seed other bytes of the same sizes
under the same law.
"""

import os

import numpy as np

IDS = 20260928      # the permutation's own stream, whatever the seed


def _rng(*entropy):
    return np.random.default_rng(np.random.SeedSequence(list(entropy)))


def generate(out_dir, seed, sequences, seq_len, vocab_size, exponent=1.1,
             dtype="uint16"):
    """Writes ``tokens.bin``; returns the product's data origin for it."""
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    p = ranks ** -float(exponent)
    cdf = np.cumsum(p / p.sum())
    u = _rng(int(seed), 0).random(sequences * seq_len)
    ids = np.minimum(np.searchsorted(cdf, u), vocab_size - 1)
    # rank r is not token r: a permutation spreads the frequent ids over
    # the embedding table, the same one for every seed
    perm = _rng(IDS, vocab_size).permutation(vocab_size)
    path = os.path.join(out_dir, "tokens.bin")
    perm[ids].astype(np.dtype(dtype)).tofile(path)
    return "tokens:%s:%d:%s" % (path, seq_len, dtype)
