"""Device time of the service's program per request, from the trace's
``XLA Modules`` line (programs named after ``serve_step``). Layer: the
model step (``models/``)."""


def read(run):
    if run.trace is None:
        return None
    secs, runs = run.trace.module_time("serve_step")
    return secs / runs * 1e3 if runs else None
