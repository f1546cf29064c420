"""The paper's objective over the window: the mean served cost per
request (approximation cost plus the level's retrieval cost, or h_model
on a miss), in the engine's cost unit."""


def read(ctx):
    return ctx.mean_cost
