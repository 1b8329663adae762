"""Client executor: programs JAX built inside the measured window, compiled
or loaded from the persistent cache alike (a ``jax.monitoring`` listener on
the backend-compile event).  Set-up warms every shape the traffic draws,
so any count here is a shape it did not foresee."""


def read(ctx):
    return float(ctx["compiles_in_window"])
