"""Forward and backward operations the window's training steps require
(the architecture module's ``flops_per_step``, sparse stacks at 2 x nnz)
per second of the window, over the chip's bf16 peak, in %."""


def read(out):
    if out.peaks is None or not out.counters.get("window_s"):
        return None
    rate = out.counters["flops"] / out.counters["window_s"]
    return 100.0 * rate / out.peaks.bf16_flops
