"""Share of the k x k convs' roofline: the least time of the work of the
convs with a kernel wider than 1 over the device time of the conv
operations under those convs' ``smof.conv:<vertex>`` scopes
(``bench/scopes.py``), the line-buffer kernel and XLA's conv of the stem
alike.

The least time is the longer of two: those convs' FLOPs (``bench/work.py``,
from the layer shapes) for the window's frames at the chip's peak FLOP/s,
and the bytes of those operations' arrays in HBM (``ops.hbm_bytes``) at its
peak HBM bytes/s.  A program that names no such scope reads nothing."""
from bench import ops, scopes, work
from bench.reference import per_dim


def read(m):
    wide = {L["name"] for L in m.net
            if L["kind"] == "conv" and "shape" in L
            and max(per_dim(L, "k")) > 1}
    names = {f"{scopes.PREFIX}conv:{n}" for n in wide}

    def mine(op):
        return ops.is_conv(op) and scopes.scope(op) in names

    busy = m.trace.op_seconds(mine)
    hbm = m.trace.op_total(mine, ops.hbm_bytes)
    if m.frames <= 0 or busy <= 0 or hbm is None:
        return None
    flops = sum(c["flops"] for c in work.convs(m.net) if c["name"] in wide)
    least = max(flops * m.frames / m.peaks["flops_per_s"],
                hbm / m.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
