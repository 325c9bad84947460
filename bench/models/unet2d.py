"""Plain description of UNet on a 2-D grid, as published.

Ronneberger et al. (arXiv:1505.04597), at the SMOF paper's 3x368x480
input (arXiv:2403.18921, Table III): per encoder level two 3x3 convs +
ReLU (channels ``base * 2**level``) and a 2x2 max pool between levels; per
decoder level a 2x2 stride-2 up-conv to half the channels, a concat of the
encoder skip of that level with it (skip first), and two 3x3 convs + ReLU;
a final 1x1 conv to the classes.  The 3x3 convs pad by 1, so the skips
need no crop.  The convs carry no bias, as the program's graph
(``repro.core.builders.build_unet``) has none.

Vertices are numbered ``<kind>_<n>`` in build order, as that graph names
them, so weights and evicted edges can be named alike.
"""
from __future__ import annotations


def layers(input_hw: list[int], cin: int, base: int, levels: int,
           n_classes: int) -> list[dict]:
    net: list[dict] = []

    def add(kind, inputs, c, shape, cout=None, **geometry):
        name = f"{kind}_{len(net) + 1}"
        net.append({"name": name, "kind": kind, "inputs": list(inputs),
                    "cin": c, "cout": cout or c, "shape": list(shape),
                    **geometry})
        return name

    hw, c = list(input_hw), cin
    prev = add("input", [], cin, hw)
    skips = []
    for lv in range(levels):
        cout = base * 2 ** lv
        for _ in range(2):
            prev = add("conv", [prev], c, hw, cout, k=3)
            prev = add("act", [prev], cout, hw)
            c = cout
        if lv < levels - 1:
            skips.append((prev, c))
            half = [n // 2 for n in hw]
            prev = add("pool", [prev], c, hw, op="max", k=2, shape_out=half)
            hw = half
    for lv in reversed(range(levels - 1)):
        cout = base * 2 ** lv
        double = [n * 2 for n in hw]
        prev = add("deconv", [prev], c, hw, cout, k=2, shape_out=double)
        hw = double
        skip, sc = skips.pop()
        prev = add("concat", [skip, prev], sc + cout, hw)
        c = sc + cout
        for _ in range(2):
            prev = add("conv", [prev], c, hw, cout, k=3)
            prev = add("act", [prev], cout, hw)
            c = cout
    prev = add("conv", [prev], c, hw, n_classes, k=1)
    add("output", [prev], n_classes, hw)
    return net
