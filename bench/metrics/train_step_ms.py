"""Device time of one execution of the train-step program (training step
layer), mean over its executions in the traced window."""
from harness import trace as TR

pred = lambda n: "train_step" in n


def read(out):
    t = out.trace
    if t is None or not t.modules:
        return None
    n = TR.count_matching(t.modules[0], t.lo, t.hi, pred)
    secs = TR.matching_seconds(t.modules[0], t.lo, t.hi, pred)
    return secs / n * 1e3 if n else None
