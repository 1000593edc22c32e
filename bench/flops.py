"""Operations and bytes the algorithm needs, counted from shapes.

A multiply-add is two FLOPs.  Only matrix products and convolutions are
counted; bias adds, ReLU, pooling, softmax and the optimizer's update are
left out (each under 1% of a layer's products at these widths).  Bytes are
the least the chip has to move: every input read once and every output
written once, in float32.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = [
    "cnn_layer_macs",
    "cnn_forward_flops",
    "cnn_profile_flops",
    "cnn_train_flops",
    "round_flops",
    "init_flops",
    "eq14_kernels",
    "peaks",
    "roofline_seconds",
    "rounds_flops",
]

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``.  A device
    that is not in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def cnn_layer_macs(cfg: dict) -> dict:
    """Multiply-adds per sample of each layer of the 2-conv/2-FC CNN
    (5x5 'SAME' convolutions, two 2x2 pools)."""
    h, w = cfg["image_hw"]
    c1, c2 = cfg["channels"]
    flat = (h // 4) * (w // 4) * c2
    return {
        "conv1": h * w * c1 * 25 * 1,
        "conv2": (h // 2) * (w // 2) * c2 * 25 * c1,
        "fc1": flat * cfg["fc1_dim"],
        "fc2": cfg["fc1_dim"] * cfg["num_classes"],
    }


def cnn_forward_flops(cfg: dict) -> int:
    return 2 * sum(cnn_layer_macs(cfg).values())


def cnn_profile_flops(cfg: dict) -> int:
    """Forward up to the FC-1 pre-activation (the eq.-11 profile)."""
    m = cnn_layer_macs(cfg)
    return 2 * (m["conv1"] + m["conv2"] + m["fc1"])


def cnn_train_flops(cfg: dict) -> int:
    """One sample's forward and backward: the weight gradient of every
    layer costs its forward again, the input gradient too except for the
    first layer, whose input (the image) needs none."""
    m = cnn_layer_macs(cfg)
    return 2 * (3 * sum(m.values()) - m["conv1"])


def round_flops(cfg: dict, eval_round: bool) -> int:
    """One federation-round: k clients x n samples x E full-batch steps,
    the refresh of the cohort's losses, and the test-set forward on an
    evaluation round."""
    k, n, e = cfg["clients_per_round"], cfg["samples_per_client"], cfg["local_epochs"]
    total = k * n * e * cnn_train_flops(cfg) + k * n * cnn_forward_flops(cfg)
    if eval_round:
        total += cfg["test_samples"] * cnn_forward_flops(cfg)
    return total


def eq14_kernels(c: int, f: int) -> dict:
    """FLOPs and least bytes of the two launches of the fused eq.-(14)
    pipeline for C profiles of width F: the pairwise distances with their
    min/max statistics (2·C²·F), then the normalised Gram L = SᵀS (2·C³)."""
    return {
        "pairwise_dists_stats": {"flops": 2 * c * c * f, "bytes": 4 * (c * f + c * c)},
        "normalized_gram": {"flops": 2 * c ** 3, "bytes": 4 * (c * c + c * c)},
    }


def init_flops(cfg: dict) -> int:
    """One federation's initialisation: every client profiled, every
    client's initial loss, and the eq.-(14) kernel.  The O(C³) ``eigh``
    of the spectral cache is not counted: its operation count depends on
    the solver."""
    c, n = cfg["num_clients"], cfg["samples_per_client"]
    eq14 = sum(v["flops"] for v in eq14_kernels(c, cfg["fc1_dim"]).values())
    return c * n * (cnn_profile_flops(cfg) + cnn_forward_flops(cfg)) + eq14


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def rounds_flops(per_round, eval_every: int, rounds: int) -> int:
    """FLOPs of a federation's first ``rounds`` rounds, given the count of
    one round, ``per_round(eval_round)``: an evaluation on every
    ``eval_every``-th round."""
    evals = rounds // eval_every
    return (rounds - evals) * per_round(False) + evals * per_round(True)

