"""Token ids from a Zipf unigram distribution, drawn with numpy from the
seed.

Uniform tokens would hold the loss at ln V whatever the optimizer does.
A Zipf unigram (exponent 1 over the 50,257 published rows: ``entropy``
computes 7.5659 nats) is learnt within tens of steps, so the loss falls
by three nats inside a run and settles a little above that entropy; a
broken backward pass or optimizer leaves it higher, and the cell's loss
check (``loss_verdict`` in ``perf/run.py``) holds the settled loss under
the entropy plus the cell's ``rise``.  Rank r is token id r-1; ids at or
above ``vocab`` (the rows a program pads the table with) are never
drawn.

The same entropy is a floor.  The ids are independent draws and every
micro-batch is a fresh one, so no program's expected loss is under the
entropy, and the mean over a batch of n tokens scatters about its
expectation by ``batch_sd``.  A loss well under the floor means the
program saw its targets or reports something else than the mean
cross-entropy.  That rests on fresh batches: a run that takes more
micro-batches than the pool holds meets the first ones again.  A pool of
128 optimizer steps wraps in a 20 s window (two warm-up steps before it)
once a step takes under about 155 ms; the fastest cell's step is 245 ms,
83 steps a run, so no cell is there yet.  A cell that gets there wants a
larger ``pool_steps`` in a traffic file of its own.
"""

import numpy as np


def _cdf(params, vocab):
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(params["exponent"]))
    return cdf / cdf[-1]


def make(params, rows, vocab, seed):
    """int32 [pool_steps, rows, seq]: one batch per step, the same for
    the same seed.  ``params`` is the traffic file's object."""
    cdf = _cdf(params, vocab)
    rng = np.random.default_rng([int(seed), 0x7A697066])
    draws = rng.random((int(params["pool_steps"]), rows, int(params["seq"])))
    # a draw of exactly 1.0 cannot occur; the clip guards rounding in cdf
    return np.minimum(np.searchsorted(cdf, draws), vocab - 1).astype(np.int32)


def _surprise(params, vocab):
    """(p, -ln p) of every id, p being the step of ``make``'s own cdf at
    that id: what ``make`` draws from, not a formula beside it."""
    p = np.diff(_cdf(params, vocab), prepend=0.0)
    return p, -np.log(p)


def entropy(params, vocab):
    """Nats a token: the least expected cross-entropy of any predictor
    of these ids."""
    p, surprise = _surprise(params, vocab)
    return float(np.sum(p * surprise))


def batch_sd(params, vocab, tokens):
    """Standard deviation of the mean of -ln p over ``tokens`` draws: how
    far the loss of one batch lies from its expectation for a predictor
    that has the distribution right."""
    p, surprise = _surprise(params, vocab)
    mean = np.sum(p * surprise)
    return float(np.sqrt(np.sum(p * (surprise - mean) ** 2) / tokens))
