"""parallel layer: rows the expert layer's segment walk moved (segments
walked x rows a segment: what its gathers, grouped matmuls and scatter-adds
cost by) over the rows of the lossless bound it would move without the walk,
over the window's steps and all expert layers. None where a step has no such
counter (a program from before the walk)."""


def read(run):
    counters = run.samples.get("counters")
    if not counters or any("moe_rows_walked" not in c or "moe_rows_bound" not in c
                           for c in counters):
        return None
    bound = sum(c["moe_rows_bound"] for c in counters)
    walked = sum(c["moe_rows_walked"] for c in counters)
    return 100.0 * walked / bound if bound > 0 else None
