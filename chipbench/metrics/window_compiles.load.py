"""XLA compiles inside the measured window (JAX's backend-compile
events): programs the warm-up did not meet, or that the system compiles
anew for each call."""


def read(r):
    return r.window_compiles
