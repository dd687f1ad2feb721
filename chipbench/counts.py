"""Operations of a round, counted from the configuration's shapes by its
model family (``families/<family>.py``).

Model FLOPs count what the algorithm needs, not what the program runs: the
forward and backward passes of every local step over every unmasked sample,
and the forward pass of the held-out set that the per-round eval makes.
Padding, recompute and the server's O(M d) release are not model FLOPs.
"""
from __future__ import annotations

from chipbench import cell


def round_flops(cfg: dict) -> int:
    """Model FLOPs of one round: local training plus the eval."""
    return cell.family(cfg).round_flops(cfg)
