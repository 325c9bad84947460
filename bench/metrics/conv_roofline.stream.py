"""Share of the convs' roofline: the least time of the conv work of the
traced window over the device time in it of the operations that implement
that work, Pallas kernels and XLA matmuls alike (``bench/ops.py``).

The least time is the longer of two: the convs' FLOPs (``bench/work.py``,
from the layer shapes) for the window's frames at the chip's peak FLOP/s,
and the bytes of those operations' arrays in HBM (``ops.hbm_bytes``, from
the shapes and memory spaces of the trace's own lines: what the compiler
keeps in VMEM moves no HBM bytes) at its peak HBM bytes/s.
"""
from bench import ops, work


def read(m):
    busy = m.trace.op_seconds(ops.is_conv)
    hbm = m.trace.op_total(ops.is_conv, ops.hbm_bytes)
    if m.frames <= 0 or busy <= 0 or hbm is None:
        return None
    least = max(work.frame_flops(m.net) * m.frames / m.peaks["flops_per_s"],
                hbm / m.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
