"""``setup_place_s`` in the Moonlight cell."""
from setup_place_s import read  # noqa: F401
