"""jit/compile: backend compiles inside the measured window (JAX's
monitoring events); every shape is warmed in set-up, so 0 is expected."""


def read(run):
    return run["compiles"]
