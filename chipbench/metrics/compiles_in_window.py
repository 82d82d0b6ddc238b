"""Layer: entry, tracer. Programs XLA was asked for inside the window
(compiled anew or loaded from the persistent cache); should read 0."""


def read(facts, name):
    return float(facts["compiles_in_window"])
