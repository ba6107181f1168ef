"""Device time of the sparse stacks (scope ``sparse``: mask select and
matmul of wo, w_gate, w_up and w_down, forward, backward and recompute) per
execution of the train-step program in the traced window, in ms."""
from harness import scopes


def read(out):
    return scopes.ms_per_execution(out, "train_step", "sparse")
