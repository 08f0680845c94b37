"""The share of the SSM-scan call sites, over every job step the adaptor
traced, that took the Pallas kernel: the program's ``ssm_scan.kernel`` and
``ssm_scan.xla`` counters (``repro.core.spans.count``, kept in the
executor's span log), kernel / (kernel + xla) x 100. A program that keeps
no such counters gives None. Layer: the model step (``models/ssm.py``)."""
from chipbench import program_spans


def read(run):
    log = program_spans.span_log(run)
    if log is None:
        return None
    kernel = log.counters.get("ssm_scan.kernel", 0)
    xla = log.counters.get("ssm_scan.xla", 0)
    if kernel + xla == 0:
        return None
    return 100.0 * kernel / (kernel + xla)
