"""Share of the traced window in which a cross-chip collective (the
gradients' all-reduce) ran on the device with no compute beside it, in %,
averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["collective_events"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
