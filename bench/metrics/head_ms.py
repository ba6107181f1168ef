"""Device time of the tied head and its cross-entropy (scope ``head``:
head matmul, logsumexp, gather, forward, backward and the chunk recompute)
per execution of the train-step program in the traced window, in ms."""
from harness import scopes


def read(out):
    return scopes.ms_per_execution(out, "train_step", "head")
