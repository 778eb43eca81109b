"""Seconds of backend compilation during set-up, summed over the
``/jax/core/compile/backend_compile_duration`` events of ``jax.monitoring``
(a program read from the persistent cache adds none)."""


def read(run):
    return run["setup"]["compile_s"]
