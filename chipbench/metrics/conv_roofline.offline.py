"""The Pallas forward conv kernels' share of their roofline, in %: the
least time the chip could take for every kernel conv of every batch
served in the traced window (per conv the larger of FLOPs over peak and
ideal bytes over HBM bandwidth) over the Mosaic kernels' device time in
the trace."""
from chipbench import counts, device


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["mosaic_events"]:
        return None
    cfg = ctx["config"]
    layers = ctx["ref"].conv_layers(cfg, (cfg["image"], cfg["image"]))
    peak = device.peaks(ctx["device_kind"])
    ideal = sum(n * counts.kernel_ideal_s(layers, ("fwd",), bucket, peak)
                for bucket, n in ctx["traced"]["by_bucket"].items())
    return 100.0 * ideal / t["mosaic_s"]
