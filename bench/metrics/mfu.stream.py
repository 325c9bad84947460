"""Whole step's share of the chip's bf16 peak: the convs' FLOPs per frame,
from the layer shapes (``bench/work.py``), times the frames of the traced
window over its length."""
from bench import work


def read(m):
    if m.frames <= 0 or m.trace.window_s <= 0:
        return None
    fps = m.frames / m.trace.window_s
    return 100.0 * work.frame_flops(m.net) * fps / m.peaks["flops_per_s"]
