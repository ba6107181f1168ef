"""Device time of the DST update's dense-gradient recompute (scope
``dst_grad``: jax.grad of the loss) per execution of the DST program in the
traced window, in ms."""
from harness import scopes


def read(out):
    return scopes.ms_per_execution(out, "dst_step", "dst_grad")
