"""Device time per frame of the standalone BFP8 quant/dequant kernels
(``kernels/bfp8.py``); the codec fused into the conv and act kernels is
inside their time and not counted here."""
from bench import ops


def read(m):
    t = m.trace.op_seconds(ops.is_bfp8)
    if m.frames <= 0 or t <= 0:
        return None
    return 1e3 * t / m.frames
