"""Device-idle milliseconds per batch inside the program's Python loops
over the batch: over the traced window, the sum over every
``serve.demand`` (``np.add.at`` on the demand counts), ``serve.respond_hits``
and ``serve.respond_misses`` span of its length less the device busy
time inside it, divided by the number of ``engine.serve`` spans. None
where the program has no such spans."""
from host_copy_ms import idle_ms_per_batch

PHASES = ("serve.demand", "serve.respond_hits", "serve.respond_misses")


def read(ctx):
    return idle_ms_per_batch(ctx, PHASES)
