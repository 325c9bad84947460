"""Operations and bytes of a frame, from the layer shapes alone.

Only the convs (1x1 channel mixing, ``y = x @ W``) count: a frame's pools,
ReLUs, upsamples and concats are elementwise or data movement, under 0.1%
of its operations.  Bytes are what a conv must move at least: its float32
input and output once, and its weight once.
"""
from __future__ import annotations

WEIGHT_KINDS = ("conv", "deconv")
F32_BYTES = 4


def convs(net: list[dict]) -> list[dict]:
    """Per conv: name, MACs, FLOPs and the least bytes it moves."""
    out = []
    for L in net:
        if L["kind"] not in WEIGHT_KINDS:
            continue
        m, cin, cout = L["m"], L["cin"], L["cout"]
        macs = m * cin * cout
        out.append({"name": L["name"], "macs": macs, "flops": 2 * macs,
                    "bytes": F32_BYTES * (m * cin + m * cout + cin * cout)})
    return out


def frame_macs(net: list[dict]) -> int:
    return sum(c["macs"] for c in convs(net))


def frame_flops(net: list[dict]) -> int:
    return sum(c["flops"] for c in convs(net))


def conv_least_time(net: list[dict], peak_flops: float,
                    peak_bytes: float) -> tuple[float, str]:
    """Seconds the convs of one frame take at the chip's peaks, each conv
    bound by its operations or its bytes, whichever is slower; and which
    bound covers most of that time (``"memory"`` or ``"compute"``)."""
    by = {"memory": 0.0, "compute": 0.0}
    for c in convs(net):
        t_mem = c["bytes"] / peak_bytes
        t_flop = c["flops"] / peak_flops
        if t_mem >= t_flop:
            by["memory"] += t_mem
        else:
            by["compute"] += t_flop
    return by["memory"] + by["compute"], max(by, key=by.get)
