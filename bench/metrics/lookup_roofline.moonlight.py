"""``lookup_roofline`` in the Moonlight cell."""
from lookup_roofline import read  # noqa: F401
