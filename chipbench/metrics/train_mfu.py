"""Training FLOPs (forward and backward, analytic, recomputation not
counted) of the trainer steps in the window, the step under way at its
close pro rata, per second of the window, as a share of the chip's bf16
peak. Layer: the model step."""
from chipbench import flops, peaks


def read(run):
    work = run.train_work()
    if not work:
        return None
    flop = sum(flops.train_step(run.cell.cfg["flops"], t.batch, t.seq) * share for t, share in work)
    return 100.0 * flop / run.seconds / peaks.peak(run.device.device_kind)
