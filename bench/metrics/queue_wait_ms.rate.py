"""Mean time from a request's due time until its batch was sent, over
the requests of the traced part of the window (open-loop mixes only)."""


def read(ctx):
    return ctx.queue_wait_ms
