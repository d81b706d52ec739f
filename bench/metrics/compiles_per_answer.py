"""Backend compiles (``jax.monitoring``'s backend-compile events, which
include loads from the persistent cache) inside the window, per answer."""


def read(run):
    if not run.answers:
        return None
    return len(run.compiles) / run.answers
