"""Operations and bytes of a conv net, from its layer list alone.

A layer list is what a configuration's reference gives
(``conv_layers``): per conv its input plane (h, w), channels (c, k),
filter (r, s), stride, padding, output plane (p, q) and whether a residual
is added in the epilogue.  All counts are per image unless a batch is
given, f32 (4 bytes an element), and do not depend on how the program
implements the layers.
"""
from __future__ import annotations

ELEM = 4


def conv_flops(lay: dict) -> float:
    """Multiply-adds of one conv over one image, counted as 2 operations."""
    return 2.0 * lay["p"] * lay["q"] * lay["k"] * lay["c"] * lay["r"] \
        * lay["s"]


def forward_flops(layers: list[dict], classifier: tuple[int, int]) -> float:
    """One image's forward: every conv plus the dense classifier."""
    c, k = classifier
    return sum(conv_flops(lay) for lay in layers) + 2.0 * c * k


def train_flops(layers: list[dict], classifier: tuple[int, int]) -> float:
    """One image's training step: forward, backward-data and weight update
    of every layer (3x forward), less the first conv's backward-data pass,
    whose result (the gradient of the image) nobody needs."""
    return 3.0 * forward_flops(layers, classifier) - conv_flops(layers[0])


def on_kernel(lay: dict) -> bool:
    """Whether the program runs this conv through its Pallas kernels: both
    channel counts a multiple of 8 (its dispatch rule; the C=3 stem is
    left to XLA)."""
    return lay["c"] % 8 == 0 and lay["k"] % 8 == 0


def _acts(lay: dict) -> tuple[float, float, float]:
    """(input, output, weight) elements of one conv over one image."""
    return (lay["h"] * lay["w"] * lay["c"], lay["p"] * lay["q"] * lay["k"],
            lay["r"] * lay["s"] * lay["c"] * lay["k"])


def pass_bytes(lay: dict, kind: str, batch: int) -> float:
    """Bytes one pass of a conv must move at least, at ``batch`` images.
    fwd: read x and w (and the residual when fused), write y.  bwd (data):
    read dy and w, write dx.  wu: read x and dy, write dw."""
    x, y, w = _acts(lay)
    if kind == "fwd":
        moved = batch * (x + y * (2 if lay["residual"] else 1)) + w
    elif kind == "fwd_train":           # epilogue not fused in training
        moved = batch * (x + y) + w
    elif kind in ("bwd", "wu"):
        moved = batch * (x + y) + w
    else:
        raise ValueError(kind)
    return ELEM * moved


def kernel_ideal_s(layers: list[dict], passes: tuple[str, ...], batch: int,
                   peak: dict) -> float:
    """Least time the chip could take for the given passes of every conv
    the program runs on its kernels, at ``batch`` images: per pass the
    larger of operations over peak FLOP/s and bytes over HBM bandwidth,
    summed.  The stem's backward-data pass is not run, so not counted."""
    total = 0.0
    for i, lay in enumerate(layers):
        if not on_kernel(lay):
            continue
        for kind in passes:
            if kind == "bwd" and i == 0:
                continue
            flops = conv_flops(lay) * batch
            total += max(flops / peak["flops"],
                         pass_bytes(lay, kind, batch) / peak["hbm_bw"])
    return total


def kernel_launches(layers: list[dict], passes: tuple[str, ...]) -> int:
    """Kernel launches the program makes for those passes: one per conv and
    pass, except a strided conv's backward-data with a filter wider than
    1, which runs one forward launch per stride phase (stride^2)."""
    n = 0
    for i, lay in enumerate(layers):
        if not on_kernel(lay):
            continue
        for kind in passes:
            if kind == "bwd":
                if i == 0:
                    continue
                n += lay["stride"] ** 2 if (lay["stride"] > 1
                                            and lay["r"] > 1) else 1
            else:
                n += 1
    return n
