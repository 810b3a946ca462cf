"""``jit.compiles`` summed over the window's steps: every program JAX built
or read from its persistent cache, plain ``jax.jit`` sites and eager ops
included (``compiles_in_window`` sees the compile plane's registry alone).
A program that counts them writes the counter even at 0; without it there
is nothing to read."""
UNIT = "count"


def read(cell):
    if cell.telemetry is None:
        return None
    value = cell.telemetry["counters"].get("jit.compiles")
    return None if value is None else float(value)
