"""Device time per frame of the element-wise vertices (act, pool,
upsample, add, mul): the Pallas kernels and XLA fusions under their
``smof.<kind>:<vertex>`` scopes that move no layout (``bench/scopes.py``).
The BFP8 egress fused into an act kernel is inside its time."""
from bench import scopes


def read(m):
    return scopes.ms_per_frame(m, "eltwise")
