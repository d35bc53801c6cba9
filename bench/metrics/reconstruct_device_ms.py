"""Device time of the reconstruction programs per request answered in
the profiled part of the window, in ms (profiler trace).  The family
``reconstruct`` is given by the mix's ``trace.families`` regular
expression over XLA module names: the two-phase, evolve, reconstruct
and measure programs (``jit_batch_*two_phase*``, ``jit_batch_evolve``,
``jit_batch_measure``, ``jit_reconstruct_*``)."""
from harness.spans import family_ms_per_answer


def read(ctx):
    return family_ms_per_answer(ctx, "reconstruct")
