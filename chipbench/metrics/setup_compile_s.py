"""Seconds of XLA compilation during set-up (persistent-cache loads
included), from JAX's ``backend_compile_duration`` events. Layer: the
adaptor (``core/adaptor.py``), which compiles each job's step to profile it."""


def read(run):
    return run.compile_setup_s
