"""Device time of the layer scan itself (scope ``blocks``, innermost:
slices and dynamic-update-slices of the stacked layers, ln2, SwiGLU,
residual adds) per execution of the train-step program in the traced
window, in ms."""
from harness import scopes


def read(out):
    return scopes.ms_per_execution(out, "train_step", "blocks")
