"""Backend compiles inside the measured window (JAX's monitoring
events); every shape the window uses is warmed in set-up, so 0."""


def read(ctx):
    return float(ctx["window_compiles"])
