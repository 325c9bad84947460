"""Device time per frame of output assembly: the ops under the program's
``smof.emit`` scope (``bench/scopes.py``), the stacking of each tick's
output into the step's ``(B, L)`` result and its final slice."""
from bench import scopes


def read(m):
    return scopes.ms_per_frame(m, "emit")
