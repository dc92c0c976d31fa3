"""The share of the visits' buffer that is live: visits to the experts
held here over every visit there could be (tokens x
``num_experts_per_tok``), summed over the expert layers, over everything
the window ran: from the program's counter ``net.expert_tokens`` (first
reading to last). The grouped products run over the whole buffer's
shape and multiply the live rows only; a sixteenth is what an even
routing gives a chip that holds a sixteenth of the experts."""


def read(ctx):
    run, cfg = ctx["run"], ctx["cfg"]
    reads = run.get("counter_reads") or ()
    if len(reads) < 2 or not run.get("seq"):
        return None
    (n0, c0), (n1, c1) = reads[0], reads[-1]
    first, count = cfg.get("held") or (0, cfg["num_experts"])
    held = sum(sum(b[first:first + count]) - sum(a[first:first + count])
               for a, b in zip(c0, c1))
    rows = (n1 - n0) * run["batch"] * run["seq"] * \
        cfg["num_experts_per_tok"] * len(c0)
    return 100.0 * held / rows if rows else None
