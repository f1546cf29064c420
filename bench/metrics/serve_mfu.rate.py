"""``serve_mfu`` in an open-loop cell, where it moves the latency
tail."""
from serve_mfu import read  # noqa: F401
