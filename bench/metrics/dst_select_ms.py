"""Device time of the DST update's selection (scope ``dst_select``:
bisection thresholds, per-column ranks, ablation) per execution of the DST
program in the traced window, in ms."""
from harness import scopes


def read(out):
    return scopes.ms_per_execution(out, "dst_step", "dst_select")
