"""The Pallas weight-update kernels' share of their roofline in training, in
%: the least time the chip could take for the weight-update pass of every
kernel conv of every traced step, per chip, over the device time per chip
of the kernels named ``conv_wu`` in the trace.  With the other two passes'
shares it splits ``conv_roofline.train``: their ideal times and device
times add up to its own."""
from chipbench import scopes


def read(ctx):
    return scopes.pass_roofline(ctx, "conv_wu", "wu")
