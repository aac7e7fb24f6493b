"""Programs JAX compiled or loaded from its cache between the window's
start and its end (JAX's own monitoring events). Expected: 0."""


def read(ctx):
    return float(ctx.facts["compiles"])
