"""XLA compiles inside the window (JAX's ``backend_compile_duration``
events); set-up compiles every shape first, so this should read 0."""


def read(ctx):
    return ctx["compiles"]
