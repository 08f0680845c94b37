"""Mean host time of the service's input in the window (in a traced run,
its traced part): the program's ``session.data_fn`` spans of the service's
iterations (building a request's tokens). Layer: the session
(``core/session.py``)."""
from chipbench import program_spans


def read(run):
    if run.service is None:
        return None
    spans = program_spans.in_window(run, "session.data_fn", job=run.service.name)
    return program_spans.mean_ms([s.t1 - s.t0 for s in spans])
