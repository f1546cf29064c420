"""``window_compiles`` in the Moonlight cell."""
from window_compiles import read  # noqa: F401
