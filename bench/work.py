"""Operations and bytes of a frame, from the layer shapes alone.

Only the convs and deconvs count: a frame's pools, activations,
upsamples, element-wise products and concats are element-wise or data
movement, well under 1% of its operations.  A conv's MACs are
``prod(out extent) * prod(k) * cin / groups * cout``, a deconv's
``prod(in extent) * prod(k) * cin * cout``; a 1-D layer's (``m``
positions, no ``shape``) ``m * cin * cout``.  Bytes are what a layer must
move at least: its float32 input and output once, and its weights (a bias
with them) once.
"""
from __future__ import annotations

import math

from bench.reference import WEIGHT_KINDS, out_shape, weight_shapes

F32_BYTES = 4


def convs(net: list[dict]) -> list[dict]:
    """Per conv: name, MACs, FLOPs and the least bytes it moves."""
    shapes = weight_shapes(net)
    out = []
    for L in net:
        if L["kind"] not in WEIGHT_KINDS:
            continue
        name = L["name"]
        if "shape" in L:
            n_in, n_out = math.prod(L["shape"]), math.prod(out_shape(L))
        else:
            n_in = n_out = L["m"]
        w = math.prod(shapes[name])
        macs = (n_out if L["kind"] == "conv" else n_in) * w
        moved = (n_in * L["cin"] + n_out * L["cout"] + w
                 + math.prod(shapes.get(f"{name}.bias", (0,))))
        out.append({"name": name, "macs": macs, "flops": 2 * macs,
                    "bytes": F32_BYTES * moved})
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
