"""Wall time of the set-up placement: the warm-start solve over the
demand history and its install into the engine."""


def read(ctx):
    return ctx.place_s
