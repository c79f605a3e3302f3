"""kvcache layer: largest share of the page pool reserved at a step boundary."""


def read(run):
    s = run.samples
    if "min_free_pages" not in s:
        return None
    return 100.0 * (1.0 - s["min_free_pages"] / s["pool_pages"])
