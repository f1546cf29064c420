"""``mean_cost`` in the Moonlight cell."""
from mean_cost import read  # noqa: F401
