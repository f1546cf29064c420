"""``hit_rate`` in the Moonlight cell."""
from hit_rate import read  # noqa: F401
