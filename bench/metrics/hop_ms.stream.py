"""Device time per frame of the evicted payloads' copies to host memory
and back (``bench/ops.py``: the off-chip hop), as the step's own line of
ops shows them: the copy-starts that issue a transfer and the copy-dones
that wait for it to land."""
from bench import ops


def read(m):
    t = m.trace.op_seconds(ops.is_hop)
    if m.frames <= 0 or t <= 0:
        return None
    return 1e3 * t / m.frames
