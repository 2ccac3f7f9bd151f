"""Packed kernels' share of their roofline in the traced window: the least
time of every packed kernel call the window dispatched (operations over
peak or bytes over bandwidth, whichever is larger, from the operands'
shapes and dtypes) over the device time of the ``demm`` kernels."""

from chipbench.stats import roofline_percent as read  # noqa: F401
