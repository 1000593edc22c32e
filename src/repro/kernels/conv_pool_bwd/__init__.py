"""Pallas TPU kernel: the backward of a first convolution stage
(conv + bias → ReLU → 2×2/2 max-pool) in one pass over the conv output."""
