"""The Pallas forward kernels' share of their roofline in training, in
%: the least time the chip could take for the forward pass of every
kernel conv of every traced step, per chip, over the device time per chip
of the kernels named ``conv_fwd`` in the trace.  With the other two passes'
shares it splits ``conv_roofline.train``: their ideal times and device
times add up to its own."""
from chipbench import scopes


def read(ctx):
    return scopes.pass_roofline(ctx, "conv_fwd", "fwd_train")
