"""parallel layer: the busiest held expert's choices over the mean held
expert's, both summed over layers and the window's steps (1.0 = even)."""


def read(run):
    counters = run.samples.get("counters")
    if not counters:
        return None
    mean = sum(c["moe_held_load_mean"] for c in counters)
    return sum(c["moe_held_load_max"] for c in counters) / mean if mean > 0 else None
