"""XLA programs compiled or loaded from the persistent cache inside the
measured window (``jax.monitoring`` events); 0 when set-up warmed every
shape."""


def read(record):
    return record["window_compiles"]
