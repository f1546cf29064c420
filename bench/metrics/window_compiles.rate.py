"""``window_compiles`` in an open-loop cell, where it moves the latency
tail."""
from window_compiles import read  # noqa: F401
