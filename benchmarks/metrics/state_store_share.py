"""kvcache layer: of the sequence state the cache manager held at the page
pool's peak in the traced window, the share that is RECURRENT state: the
state store's bytes (every slot's, as allocated) over those plus the bytes
of the K/V pages in use at the peak."""


def read(run):
    traced = run.samples.get("traced") or {}
    cache, peak = traced.get("cache"), traced.get("pool_peak_in_use")
    if not cache or peak is None:
        return None
    state = cache["state_store_bytes"]
    return 100.0 * state / (state + peak * cache["page_bytes"])
