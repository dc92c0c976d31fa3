"""The comparison that decides ``correct`` for a language-model training
cell: numbers, never verdicts (``lib/compare.judge`` holds each against
its limit, an upper one).

Adam's first move does not give the gradient back, so each checked step's
gradient is read where the program keeps it (``Parameter.grad()``) and
compared leaf by leaf with the reference's own gradient of that step, by
the norm of the DIFFERENCE: ``|got - want|`` over the larger of that
leaf's ``|want|`` and the median leaf's. The experts' matrices are held
apart from the other leaves: an expert's gradient is a sum over the
tokens routed to it, so it also measures which tokens the expert saw.
Arrays are host numpy arrays of any float type."""
import statistics

import numpy as np


def _f32(a):
    return np.asarray(a).astype(np.float32, copy=False)


def leaf_gaps(got, want, names, base=None, scale=1.0):
    """{leaf: gap}: ``base`` (the weights before the first step) is taken
    from both sides first where given; ``scale`` multiplies ``got``. A
    NaN reads as infinity."""
    diff, norm = {}, {}
    for n in names:
        w = _f32(want[n])
        g = _f32(got[n]) * np.float32(scale)
        if base is not None:
            b = _f32(base[n])
            w, g = w - b, g - b
        diff[n] = float(np.linalg.norm((g - w).ravel()))
        norm[n] = float(np.linalg.norm(w.ravel()))
    med = statistics.median(norm.values())
    gaps = {n: diff[n] / max(norm[n], med, 1e-30) for n in names}
    return {n: g if g == g else float("inf") for n, g in gaps.items()}


def worst(gaps, names=None):
    """(gap, leaf) of the widest among ``names`` (all by default)."""
    where = max(names or gaps, key=gaps.get)
    return gaps[where], where


def route_disagree_pct(got, want):
    """100 minus the share of (token, slot) selections that agree:
    ``got`` / ``want`` are int arrays [tokens, k] of expert ids, compared
    as sets per token (two near-equal scores may swap slots)."""
    got, want = np.asarray(got), np.asarray(want)
    k = want.shape[1]
    if got.shape[0] != want.shape[0]:
        return 100.0
    same = (got[:, :, None] == want[:, None, :]).any(axis=1).sum()
    return 100.0 * (1.0 - float(same) / (want.shape[0] * k))


def training_numbers(got, want, replay, base, trainable, experts):
    """``got`` / ``want``: {"losses": [...], "grads": [{leaf: array} of
    each checked step], "weights": {leaf: array after the checked steps},
    "selections": [[tokens, k] of each expert layer at the first step]}.
    ``got`` may give ``grad_scale``: gluon's gradient is the sum over the
    batch and the trainer divides it, so the program's is read times
    1 / batch. ``base`` {leaf: array before the first step}; ``experts``:
    the leaves that are experts' matrices.

    ``grad_gap`` / ``grad2_gap`` / ``grad3_gap``: each step's gradient
    against the reference's own, worst leaf; ``grad_gap_rest``: the first
    step's, worst leaf that is no expert's. ``move_ref_gap``: the move
    over the checked steps against the reference's own move. Adam's first
    moves are lr x sign(g) nearly everywhere, so a gradient's last bit
    flips a whole step and this number reads the gradients' noise, not
    the optimizer: ``move_gap`` holds ``got``'s weights to ``replay``
    {leaf: array}, the reference's optimizer run from ``base`` over
    ``got``'s OWN gradients. ``route_disagree_pct``: the first expert
    layer's. Returns (numbers, detail): the worst leaves, every leaf's
    first-step gap, and every expert layer's disagreement."""
    out, detail = {}, {"worst_leaf": {}}
    scale = got.get("grad_scale", 1.0)
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        gap = abs(a - b) / abs(b)
        out["loss%d_gap" % (i + 1)] = gap if gap == gap else float("inf")
    rest = [n for n in trainable if n not in experts]
    for i, (g, w) in enumerate(zip(got["grads"], want["grads"])):
        gaps = leaf_gaps(g, w, trainable, scale=scale)
        name = "grad%s_gap" % ("" if i == 0 else i + 1)
        out[name], detail["worst_leaf"][name] = worst(gaps)
        if i == 0:
            detail["grad_gap_by_leaf"] = gaps
            out["grad_gap_rest"], detail["worst_leaf"]["grad_gap_rest"] = \
                worst(gaps, rest)
    for name, held_to in (("move_gap", replay),
                          ("move_ref_gap", want["weights"])):
        out[name], detail["worst_leaf"][name] = worst(
            leaf_gaps(got["weights"], held_to, trainable, base=base))
    by_layer = [route_disagree_pct(a, b) for a, b in
                zip(got["selections"], want["selections"])]
    out["route_disagree_pct"] = by_layer[0]
    detail["route_disagree_pct_by_layer"] = by_layer
    return out, detail
