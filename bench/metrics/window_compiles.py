"""Programs compiled, or loaded from the persistent compile cache,
inside the window: shapes the warm-up could not reach (JAX monitoring:
one backend-compile event per program, a cache load included)."""


def read(ctx):
    return None if ctx.compiles is None else ctx.compiles["compiles"]
