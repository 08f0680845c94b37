"""A request's model FLOPs (analytic, from the configuration file) over the
service program's device time per request, as a share of the chip's bf16
peak. Layer: the model step."""
from chipbench import flops, peaks


def read(run):
    if run.trace is None:
        return None
    secs, runs = run.trace.module_time("serve_step")
    if not runs:
        return None
    svc = run.cell.mix["service"]
    work = flops.prefill(run.cell.cfg["flops"], int(svc["batch"]), int(svc["seq"]))
    return 100.0 * work / (secs / runs) / peaks.peak(run.device.device_kind)
