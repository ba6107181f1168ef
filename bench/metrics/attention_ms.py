"""Device time of attention (scope ``attention``: norms, dense q/k/v,
qk-norm, RoPE and chunked attention; its wo counts as ``sparse``) per
execution of the train-step program in the traced window, in ms."""
from harness import scopes


def read(out):
    return scopes.ms_per_execution(out, "train_step", "attention")
