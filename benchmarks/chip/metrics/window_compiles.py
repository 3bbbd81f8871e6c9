"""Backend compiles inside the traced window (JAX monitoring): set-up
should have built every program the window runs."""


def read(r):
    return float(r.compiles)
