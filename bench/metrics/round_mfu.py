"""round_mfu (%): the matmul FLOPs one round of the job needs
(``harness.counts.round_matmul_flops``) times the rounds of the traced
window, over the window's length times the chips times the chip's peak."""
from harness import counts, peaks


def read(run):
    t = run.trace
    if t.window_s <= 0:
        return None
    flops = counts.round_matmul_flops(run.job, run.info) * run.rounds
    peak = peaks.peaks(run.device_kind)["flops"]
    return 100.0 * flops / (t.window_s * run.chips * peak)
