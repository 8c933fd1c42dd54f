"""The system under test for a ResNet v1.5 configuration: the program's own
topology and graph executor, at the configuration's sizes."""
from __future__ import annotations


def build(cfg: dict):
    """A ``GxM`` executor of the configuration, on the program's default
    kernel backend."""
    from repro.graph import GxM, resnet50
    fixed = {"widths": [64, 128, 256, 512], "expansion": 4,
             "stem": {"width": 64, "kernel": 7, "stride": 2},
             "stem_pool": {"window": 3, "stride": 2, "padding": 1}}
    if any(cfg[k] != v for k, v in fixed.items()):
        raise ValueError("the program's resnet topology has the published "
                         "widths and stem only")
    nl = resnet50(cfg["num_classes"], stages=tuple(cfg["stages"]))
    return GxM(nl, num_classes=cfg["num_classes"])
