"""Operations and bytes the algorithm needs, from the configuration's shapes.

Shared by the per-layer readers.  Nothing here reads the program: the
forward FLOPs come from the configuration's plain reference module.
"""
from __future__ import annotations

import importlib

import numpy as np


def reference_module(cfg: dict):
    """``chipbench/configs/<cfg["reference"]>.py``."""
    return importlib.import_module(f"chipbench.configs.{cfg['reference']}")


def params_per_node(cfg: dict) -> int:
    """Weights plus biases of one node's model."""
    return sum(int(np.prod(s)) + s[-1] for _, s, _ in reference_module(cfg).layout(cfg))


def forward_flops(cfg: dict) -> int:
    """FLOPs of one sample's forward pass."""
    return int(reference_module(cfg).forward_flops(cfg))


def train_flops_per_node_round(cfg: dict, traffic: dict) -> int:
    """Forward and backward of every local sample of one node in one round:
    3 × forward FLOPs × local_batches × batch_size.  Evaluation, the mix
    and recomputation are not counted."""
    samples = traffic["local_batches"] * traffic["batch_size"]
    return 3 * forward_flops(cfg) * samples


def mix_bytes(n: int, d: int) -> int:
    """Least HBM traffic of one DecAvg mix: read and write every node's
    float32 parameters once."""
    return 2 * n * d * 4


def mix_flops(n: int, d: int, directed_edges: int) -> int:
    """One multiply-add per received row element, self term included."""
    return 2 * (directed_edges + n) * d


def mix_least_seconds(n: int, d: int, directed_edges: int, peaks: dict) -> float:
    """The mix's roofline time: the larger of its byte and FLOP bounds."""
    return max(
        mix_bytes(n, d) / peaks["hbm_bytes_per_s"],
        mix_flops(n, d, directed_edges) / peaks["flops"],
    )
