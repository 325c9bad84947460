"""Device operations in the traced window per frame in it."""


def read(m):
    if m.frames <= 0 or not m.trace.n_ops:
        return None
    return m.trace.op_count() / m.frames
