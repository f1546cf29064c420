"""Share of the window's requests served by a cache level."""


def read(ctx):
    return None if ctx.hit_rate is None else 100.0 * ctx.hit_rate
