"""``prefill_pieces`` in the Moonlight cell."""
from prefill_pieces import read  # noqa: F401
