"""XLA programs compiled or loaded from the disk cache inside the timed
window (JAX's ``/jax/core/compile/backend_compile_duration`` events).
Every shape is warmed in set-up, so this should read 0."""


def read(run):
    return run["window"]["xla_programs"]
