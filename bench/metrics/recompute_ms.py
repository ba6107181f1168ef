"""Device time of the remat recompute (operations under
``rematted_computation``) per execution of the train-step program in the
traced window, in ms. It overlaps the scope metrics: the recompute of the
sparse stacks also counts in ``sparse_stacks_ms``, and so on."""
from harness import scopes


def read(out):
    return scopes.ms_per_execution(out, "train_step", "remat")
