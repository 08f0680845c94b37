"""Mean host time of the service's dispatch in the window (in a traced run,
its traced part): the program's ``session.dispatch`` spans of the
service's iterations, from the call of its compiled step to its return
(the request's tokens copied to the device and the step enqueued). Layer:
the session (``core/session.py``)."""
from chipbench import program_spans


def read(run):
    if run.service is None:
        return None
    spans = program_spans.in_window(run, "session.dispatch", job=run.service.name)
    return program_spans.mean_ms([s.t1 - s.t0 for s in spans])
