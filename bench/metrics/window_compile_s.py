"""Seconds JAX spent compiling while the window ran (its backend compile
durations, as ``jax.monitoring`` reports them), over the whole window: time
the engine's step loop lost to first-seen shapes, beside the window's
tokens per second."""


def read(run):
    return run.compile_s
