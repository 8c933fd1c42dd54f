"""The Pallas conv kernels' share of their roofline in training, in %:
the least time the chip could take for the forward, backward-data and
weight-update passes of every kernel conv of every traced step, per chip,
over the Mosaic kernels' device time per chip in the trace."""
from chipbench import counts, device


def read(ctx):
    t = ctx["trace"]
    if t is None or not t["mosaic_events"]:
        return None
    cfg = ctx["config"]
    layers = ctx["ref"].conv_layers(cfg, (cfg["image"], cfg["image"]))
    peak = device.peaks(ctx["device_kind"])
    per_step = counts.kernel_ideal_s(layers, ("fwd_train", "bwd", "wu"),
                                     ctx["mix"]["per_chip_batch"], peak)
    return 100.0 * ctx["traced"]["steps"] * per_step / t["mosaic_s"]
