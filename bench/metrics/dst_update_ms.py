"""Device time of one execution of the DST-update program (dense-gradient
recompute, per-neuron regrow, ablation), mean over its executions in the
traced window; nothing where the window holds none."""
from harness import trace as TR

pred = lambda n: "dst_step" in n


def read(out):
    t = out.trace
    if t is None or not t.modules:
        return None
    n = TR.count_matching(t.modules[0], t.lo, t.hi, pred)
    secs = TR.matching_seconds(t.modules[0], t.lo, t.hi, pred)
    return secs / n * 1e3 if n else None
