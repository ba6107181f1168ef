"""Device time of the optimizer (scope ``optimizer``: global norm, clip,
masked AdamW) per execution of the train-step program in the traced window,
in ms."""
from harness import scopes


def read(out):
    return scopes.ms_per_execution(out, "train_step", "optimizer")
