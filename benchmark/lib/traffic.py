"""The one traffic generator: a mix is a data file of parameters.

A mix fixes a pool of (prompt length, output length) pairs, drawn once
from the mix's own ``traffic_seed``; a run's ``--seed`` only orders the
pool and draws the token ids. So every seed gives the system the same
set of sizes in another order, and the pool is cycled for as long as
the window lasts.

Length distributions (``dist``): ``lognormal`` (``median``, ``sigma``)
and ``uniform``, both clipped to ``min`` .. ``max``; ``fixed``
(``value``).
"""
import numpy as np


def draw_lengths(spec, n, rng):
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if kind == "lognormal":
        vals = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    elif kind == "uniform":
        vals = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError("unknown length distribution %r" % kind)
    return np.clip(np.floor(vals), spec["min"], spec["max"]).astype(np.int64)


def pool(params):
    """The mix's pool: [(prompt_len, output_len)], the same for every
    seed."""
    rng = np.random.default_rng(params["traffic_seed"])
    n = params["pool_requests"]
    return list(zip(draw_lengths(params["prompt"], n, rng).tolist(),
                    draw_lengths(params["output"], n, rng).tolist()))


def order(params, seed):
    """This seed's order of the pool."""
    pairs = pool(params)
    perm = np.random.default_rng([int(seed), 1]).permutation(len(pairs))
    return [pairs[i] for i in perm]


def prompt_tokens(seed, k, length, vocab):
    """Token ids of the k-th request of a run: uniform in [1, vocab)."""
    rng = np.random.default_rng([int(seed), 2, int(k)])
    return rng.integers(1, vocab, length).astype(np.int32)
