"""``prefill_roofline`` in an open-loop cell, where it moves the latency
tail."""
from prefill_roofline import read  # noqa: F401
