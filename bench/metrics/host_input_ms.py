"""Device-idle milliseconds per batch while the program gathers, pads and
uploads a batch's inputs and dispatches its programs: over the traced
window, the sum over every ``serve.queries``, ``simcache.lookup``,
``serve.miss_gather`` and ``engine.prefill`` span of its length less the
device busy time inside it, divided by the number of ``engine.serve``
spans. None where the program has no such spans."""
from host_copy_ms import idle_ms_per_batch

PHASES = ("serve.queries", "simcache.lookup", "serve.miss_gather",
          "engine.prefill")


def read(ctx):
    return idle_ms_per_batch(ctx, PHASES)
