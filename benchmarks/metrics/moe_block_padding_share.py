"""parallel layer: 1 - choices routed to held experts / rows the gmm
kernels computed (occupied blocks x 256), over the window's steps: the
round-up of every expert's rows to the kernel's block quantum."""


def read(run):
    counters = run.samples.get("counters")
    if not counters:
        return None
    rows = sum(c["moe_rows_computed"] for c in counters)
    routed = sum(c["moe_routed_here"] for c in counters)
    return 100.0 * (1.0 - routed / rows) if rows > 0 else None
