"""XLA programs built inside the window (compiled, or fetched from the
persistent cache), whichever jitted function they belong to; should be 0.
Counted by the benchmark through ``jax.monitoring``; the program's own
``jit_compile`` + ``jit_recompile`` events are a subset and are printed
beside it."""


def read(records, trace, cell):
    return max(records["compiles"], records["tracked_compiles"])
