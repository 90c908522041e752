"""Programs lowered inside the window (each compiled or read from the
persistent cache); 0 when warm-up covered every shape.

Source: JAX's monitoring event for a lowering to an MLIR module.
"""


def read(w):
    return float(w.compiles)
