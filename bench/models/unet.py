"""Plain description of the executable UNet: encoder/decoder with long skips.

Ronneberger et al. (arXiv:1505.04597) as the benchmark runs it: one 1x1
conv + ReLU per encoder level (channels ``base * 2**level``), a pool that
halves the position axis between levels, and per decoder level an upsample
that doubles it, a 1x1 "deconv", a concat with the encoder skip of that
level, and a 1x1 conv + ReLU; a final 1x1 conv to the classes.

Vertices are numbered ``<kind>_<n>`` in build order, so weights and
evicted edges can be named the way the system under test names them.
"""
from __future__ import annotations


def layers(positions: int, cin: int, base: int, levels: int,
           n_classes: int) -> list[dict]:
    net: list[dict] = []

    def add(kind, inputs, c, m, cout=None, m_out=None):
        name = f"{kind}_{len(net) + 1}"
        net.append({"name": name, "kind": kind, "inputs": list(inputs),
                    "cin": c, "cout": cout or c, "m": m,
                    "m_out": m_out or m})
        return name

    m, c = positions, cin
    prev = add("input", [], cin, m)
    skips = []
    for lv in range(levels):
        cout = base * 2 ** lv
        prev = add("conv", [prev], c, m, cout)
        prev = add("act", [prev], cout, m)
        c = cout
        if lv < levels - 1:
            skips.append((prev, c))
            prev = add("pool", [prev], c, m, m_out=m // 2)
            m //= 2
    for lv in reversed(range(levels - 1)):
        cout = base * 2 ** lv
        prev = add("upsample", [prev], c, m, m_out=m * 2)
        m *= 2
        prev = add("deconv", [prev], c, m, cout)
        skip, sc = skips.pop()
        prev = add("concat", [skip, prev], sc + cout, m)
        prev = add("conv", [prev], sc + cout, m, cout)
        prev = add("act", [prev], cout, m)
        c = cout
    prev = add("conv", [prev], c, m, n_classes)
    add("output", [prev], n_classes, m)
    return net
