"""Token ids from a Zipf unigram distribution, drawn with numpy from the
seed.

Uniform tokens would hold the loss at ln V whatever the optimizer does.
A Zipf unigram (exponent 1: about 7.6 nats over 50,257 rows) is learnt
within tens of steps, so the loss falls by nats inside a run, and a
broken backward pass or optimizer shows in the cell's loss band.  Rank r
is token id r-1; ids at or above ``vocab`` (the rows a program pads the
table with) are never drawn.
"""

import numpy as np


def make(params, rows, vocab, seed):
    """int32 [pool_steps, rows, seq]: one batch per step, the same for
    the same seed.  ``params`` is the traffic file's object."""
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(params["exponent"]))
    cdf /= cdf[-1]
    rng = np.random.default_rng([int(seed), 0x7A697066])
    draws = rng.random((int(params["pool_steps"]), rows, int(params["seq"])))
    # a draw of exactly 1.0 cannot occur; the clip guards rounding in cdf
    return np.minimum(np.searchsorted(cdf, draws), vocab - 1).astype(np.int32)
