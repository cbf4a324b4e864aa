"""Programs JAX obtained inside the window, compiled or loaded from the
persistent cache (jax.monitoring); set-up should leave none to do."""


def read(run):
    return run.compiles_in_window
