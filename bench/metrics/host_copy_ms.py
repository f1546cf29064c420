"""Device-idle milliseconds per batch inside the program's blocking
device→host copies: over the traced window, the sum over every
``serve.fetch_lookup`` and ``serve.fetch_prefill`` span of its length
less the device busy time inside it, divided by the number of
``engine.serve`` spans. None where the program has no such spans.

``idle_ms_per_batch`` is shared by ``host_loop_ms`` and
``host_input_ms``; the three split ``serve_host_ms``, and their phases
do not overlap. ``idle_ms_median`` is shared by the ``*_p50_ms``
metrics, the same idle time taken batch by batch."""
import bisect
import statistics

PHASES = ("serve.fetch_lookup", "serve.fetch_prefill")


def idle_ms_per_batch(ctx, phases) -> float | None:
    t = ctx.trace
    if t is None:
        return None
    batches = t.spans("engine.serve")
    if not batches:
        return None
    idle = sum((b - a) - t.busy_s(a, b)
               for name in phases for a, b in t.spans(name))
    return 1e3 * idle / len(batches)


def idle_ms_median(ctx, phases) -> float | None:
    """The median over the traced ``engine.serve`` spans of the device
    idle ms inside those of ``phases``' spans that the batch holds (a
    batch without such a phase reads 0). None where the program has no
    such spans."""
    t = ctx.trace
    if t is None:
        return None
    batches = sorted(t.spans("engine.serve"))
    if not batches:
        return None
    starts = [a for a, _ in batches]
    idle = [0.0] * len(batches)
    for name in phases:
        for a, b in t.spans(name):
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= batches[i][1]:
                idle[i] += (b - a) - t.busy_s(a, b)
    return 1e3 * statistics.median(idle)


def read(ctx):
    return idle_ms_per_batch(ctx, PHASES)
