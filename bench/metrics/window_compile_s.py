"""Seconds spent compiling, or loading from the persistent compile
cache, inside the window: the sum the registry histogram
``jax_compile_seconds`` gained (program counter, fed by the program's
``repro.obs.compiles`` listener; absent from a program without it)."""


def read(ctx):
    if "jax_compile_seconds" not in (ctx.reg1 or {}).get("histograms", {}):
        return None
    return ctx.histogram_delta("jax_compile_seconds")[0]
