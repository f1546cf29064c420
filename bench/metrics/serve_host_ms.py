"""Host time per batch inside ``serve``: the mean over the traced
batches of the serve span's length less the device busy time inside
it."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    spans = t.spans("bench.serve")
    if not spans:
        return None
    host = [(b - a) - t.busy_s(a, b) for a, b in spans]
    return 1e3 * sum(host) / len(host)
