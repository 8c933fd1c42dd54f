"""Model FLOPs utilization of training, in %: training FLOPs per image
(3x forward less the stem's backward-data pass) times images/s (host
clock, over the run's measured window, which is never traced) over chips
times the chip's bf16 peak."""
from chipbench import counts, device


def read(ctx):
    cfg = ctx["config"]
    ref = ctx["ref"]
    layers = ref.conv_layers(cfg, (cfg["image"], cfg["image"]))
    flops = counts.train_flops(layers, ref.classifier(cfg))
    peak = device.peaks(ctx["device_kind"])["flops"]
    return 100.0 * flops * ctx["summary"]["train_images_per_s"] \
        / (ctx["chips"] * peak)
