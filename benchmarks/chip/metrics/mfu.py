"""Model FLOP utilization of the whole step, in %.

Model FLOPs of the window's trained tokens (``flops.py``: 3 x forward,
no recompute) over the window's wall time times the chips times each
chip's bf16 peak (``peaks.py``).
"""


def read(w):
    if w.wall_s <= 0:
        return None
    flops = w.tokens * w.flops_per_token
    return 100.0 * flops / (w.wall_s * w.chips * w.peak["bf16_flops"])
