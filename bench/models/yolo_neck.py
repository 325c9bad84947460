"""Plain description of the executable YOLOv8 neck and box head.

The input is the P3 feature map.  Three 1x1 conv + ReLU stages with pools
between them give P3/P4/P5; the PAN neck runs top-down (upsample, concat,
1x1 conv) and bottom-up (pool, concat, 1x1 conv); each scale then has a
box branch of 1x1 conv + ReLU + 1x1 conv, and the output concatenates the
three branches, flattened.

Vertices are numbered ``<kind>_<n>`` in build order, as the system under
test names them.
"""
from __future__ import annotations


def layers(positions: int, widths: list[int], head: int) -> list[dict]:
    net: list[dict] = []

    def add(kind, inputs, c, m, cout=None, m_out=None):
        name = f"{kind}_{len(net) + 1}"
        net.append({"name": name, "kind": kind, "inputs": list(inputs),
                    "cin": c, "cout": cout or c, "m": m,
                    "m_out": m_out or m})
        return name

    m, c = positions, widths[0]
    prev = add("input", [], c, m)
    pyramid = []
    for i, w in enumerate(widths):
        prev = add("conv", [prev], c, m, w)
        prev = add("act", [prev], w, m)
        c = w
        pyramid.append((prev, c, m))
        if i < len(widths) - 1:
            prev = add("pool", [prev], c, m, m_out=m // 2)
            m //= 2
    (p3, c3, m3), (p4, c4, m4), (p5, c5, m5) = pyramid
    up5 = add("upsample", [p5], c5, m5, m_out=m4)
    cat4 = add("concat", [p4, up5], c4 + c5, m4)
    n4 = add("conv", [cat4], c4 + c5, m4, c4)
    up4 = add("upsample", [n4], c4, m4, m_out=m3)
    cat3 = add("concat", [p3, up4], c3 + c4, m3)
    n3 = add("conv", [cat3], c3 + c4, m3, c3)
    d3 = add("pool", [n3], c3, m3, m_out=m4)
    cat4b = add("concat", [d3, n4], c3 + c4, m4)
    n4b = add("conv", [cat4b], c3 + c4, m4, c4)
    d4 = add("pool", [n4b], c4, m4, m_out=m5)
    cat5 = add("concat", [d4, p5], c4 + c5, m5)
    n5 = add("conv", [cat5], c4 + c5, m5, c5)
    outs = []
    for hd, cch, hm in ((n3, c3, m3), (n4b, c4, m4), (n5, c5, m5)):
        h = add("conv", [hd], cch, hm, head)
        h = add("act", [h], head, hm)
        outs.append(add("conv", [h], head, hm, head))
    add("output", outs, head, m3)
    return net
