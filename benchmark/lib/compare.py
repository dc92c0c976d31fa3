"""The comparisons that decide ``correct``: numbers, never verdicts.
Each number is later held against its own limit from the cell's
workload file."""
import statistics

GRAD_FLOOR = 1e-3     # of the median leaf's reference gradient norm


def worst_leaf_gap(got, ref, names):
    """Largest gap between two norms of one leaf, |got - ref|, against
    the reference's norm of that leaf or of the median leaf, whichever
    is larger. Returns (gap, leaf)."""
    med = statistics.median(ref[n] for n in names)
    worst, where = -1.0, None
    for n in names:
        gap = abs(got[n] - ref[n]) / max(ref[n], med)
        if gap != gap:              # a NaN is the worst there is
            return float("inf"), n
        if gap > worst:
            worst, where = gap, n
    return worst, where


def training_numbers(got, ref, trainable, leaves):
    """``got`` / ``ref``: {"losses": [l1, l2, l3], "grad": {leaf: norm},
    "move": {leaf: norm}}. The gradient is compared on the trainable
    leaves; the move over three steps on every leaf but those whose
    reference gradient is under GRAD_FLOOR of the median leaf's (they
    move by round-off alone)."""
    out, where = {}, {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        gap = abs(a - b) / abs(b)
        out["loss%d_gap" % (i + 1)] = gap if gap == gap else float("inf")
    out["grad_norm_gap"], where["grad_norm_gap"] = worst_leaf_gap(
        got["grad"], ref["grad"], trainable)
    med = statistics.median(ref["grad"][n] for n in trainable)
    still = {n for n in trainable if ref["grad"][n] < GRAD_FLOOR * med}
    moved = [n for n in leaves if n not in still]
    out["move_norm_gap"], where["move_norm_gap"] = worst_leaf_gap(
        got["move"], ref["move"], moved)
    return out, where


def judge(numbers, limits):
    """[(name, value, limit)] in the numbers' order, and whether every
    number that has a limit keeps to it. A limit of None means the
    number is printed and not compared."""
    rows = [(n, v, limits.get(n)) for n, v in numbers.items()]
    ok = all(lim is None or (v == v and v <= lim) for _, v, lim in rows)
    missing = [n for n in limits if n not in numbers]
    return rows, ok and not missing
