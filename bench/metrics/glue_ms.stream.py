"""Device time per frame of the executor's layout ops: pads, slices,
reshapes, broadcasts, concatenates, converts and copies, alone or as a
fusion's root, in any scope but ``smof.emit``, and XLA's copies between
HBM and VMEM (``bench/scopes.py``)."""
from bench import scopes


def read(m):
    return scopes.ms_per_frame(m, "glue")
