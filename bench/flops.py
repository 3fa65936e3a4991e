"""Work counts of one training step, from the configuration's shapes
alone, so the count is the same whatever implements the step.  A model's
FLOPs are its module's ``step_flops`` (``bench/models/<model>.py``), at
the nominal block sizes below.

Block sizes are nominal: the top level has ``batch`` destination nodes,
and each level's sources are its destinations times ``1 + fanout``
(the destinations themselves and one slot per draw).  For products at
batch 1,000 and fanouts 15/10/5 the destination sets are 1,000, 16,000
and 176,000 nodes over 16,000, 176,000 and 1,056,000 sources.
"""
from __future__ import annotations


def block_sizes(batch: int, fanouts) -> list[int]:
    """Frontier sizes, top first: ``batch`` and each level's sources."""
    sizes = [int(batch)]
    for f in fanouts:
        sizes.append(sizes[-1] * (1 + int(f)))
    return sizes


def fused_sample_bytes(batch: int, fanouts) -> int:
    """HBM bytes the fused sampler must move in one step, summed over
    levels: per seed its two ``indptr`` words, ``F`` drawn ids read and
    ``F`` written (int32), and its row count."""
    sizes = block_sizes(batch, fanouts)
    return sum(sizes[d] * (2 * 4 + 2 * 4 * int(f) + 4)
               for d, f in enumerate(fanouts))
