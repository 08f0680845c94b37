"""95th percentile of the wait from a request's due time to the start of
its iteration (``Session.run_iteration``), over the requests served. Layer:
the executor (``core/executor.py``, ``scheduler.py``)."""
from chipbench import traffic


def read(run):
    waits = run.queue_waits_ms()
    return traffic.percentile(waits, 95) if waits else None
