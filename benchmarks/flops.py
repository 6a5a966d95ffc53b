"""Operations and bytes the algorithms need, computed from shapes alone.

A multiply-accumulate is 2 floating-point operations, the unit the peak table
(peaks.json) is written in. Training counts forward + backward as 3 x forward
(one forward, one pass for the input gradient, one for the weight gradient);
recomputed operations do not count. What is particular to one configuration
(which convolutions it has) is counted by `forward_macs(args)` of its own
reference/<config>.py, from these pieces.
"""
from __future__ import annotations


def conv_out(size, stride):
    """Output extent of a SAME convolution or pool."""
    return -(-size // stride)


def conv_macs(out_h, out_w, kh, kw, cin, cout):
    return out_h * out_w * kh * kw * cin * cout


def train_flops_per_sample(forward_macs):
    return 3 * 2 * forward_macs
