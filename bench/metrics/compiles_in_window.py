"""Execution: XLA programs built inside the window (JAX's
``backend_compile_duration`` events).  Set-up warms every program the
traffic can produce, so this reads 0; anything else is compile time inside
the tails."""


def read(window):
    return float(window.compiles)
