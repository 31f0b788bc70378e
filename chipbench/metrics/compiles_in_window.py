"""XLA compile requests made inside the measured window (jax.monitoring's
backend compile events; a persistent-cache hit is a request too)."""


def read(rec):
    return rec["window"]["compiles_in_window"]
