"""parallel layer: collective time during which no other op runs on the chip,
over the traced steps' device-busy time (averaged over chips)."""


def read(run):
    if run.trace is None or run.chips < 2 or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.collective_exposed_s / run.trace.busy_s
