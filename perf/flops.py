"""Operations and bytes computed from shapes: the numerators of the
roofline shares and of the model FLOP utilisation.

Model FLOPs count the multiply-adds the algorithm needs (two FLOPs each):
convolutions and matrix products, nothing recomputed, and no elementwise
work.  Training costs three forward passes (forward, and a backward pass
twice as long).
"""
from __future__ import annotations


def conv2d_same_flops(h: int, w: int, k: int, c_in: int, c_out: int) -> int:
    """A stride-1 ``SAME`` convolution over an ``h x w`` map."""
    return 2 * h * w * c_out * k * k * c_in


def cnn_forward_flops(side: int, channels: tuple[int, ...], kernel: int,
                      hidden: int, classes: int) -> int:
    """Per row: 3x3 SAME convs, each followed by a 2x2 max pool, then a
    two-layer head over the flattened map."""
    flops, c_in, s = 0, 1, side
    for c_out in channels:
        flops += conv2d_same_flops(s, s, kernel, c_in, c_out)
        c_in, s = c_out, s // 2
    flat = c_in * s * s
    return flops + 2 * flat * hidden + 2 * hidden * classes


def train_flops(forward: int) -> int:
    return 3 * forward


def mix_aggregate_cost(c: int, f: int, g: int) -> tuple[int, int]:
    """``(flops, bytes)`` of one ``mix_aggregate`` call, ``w (G, C) @ x
    (C, F)`` in fp32: every operand read once and the output written
    once."""
    return 2 * g * c * f, 4 * (c * f + g * f + g * c)
