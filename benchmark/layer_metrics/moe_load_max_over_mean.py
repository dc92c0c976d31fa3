"""The busiest held expert's visits over the mean of the held experts',
per expert layer and averaged over the layers, over everything the window
ran: from the program's counter ``net.expert_tokens`` (first reading to
last). 1.0 is a perfectly even load; the grouped products take as long as
their rows, so this is what imbalance would cost a job whose experts sit
on different chips."""


def read(ctx):
    reads = ctx["run"].get("counter_reads") or ()
    if len(reads) < 2:
        return None
    cfg = ctx["cfg"]
    first, count = cfg.get("held") or (0, cfg["num_experts"])
    ratios = []
    for a, b in zip(reads[0][1], reads[-1][1]):
        held = [y - x for x, y in zip(a[first:first + count],
                                      b[first:first + count])]
        if sum(held):
            ratios.append(max(held) * len(held) / sum(held))
    return sum(ratios) / len(ratios) if ratios else None
