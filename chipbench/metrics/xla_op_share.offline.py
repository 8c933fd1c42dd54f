"""Device time outside the Pallas (Mosaic) kernels over device busy
time, in %, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["mosaic_events"] or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["mosaic_busy_s"] / t["busy_s"])
