"""The executor's own host time per iteration in the window (in a traced
run, its traced part): the self time of the program's ``executor.pick``
(candidates and the policy's choice) and ``executor.tick`` (the memory
manager's boundary tick) spans that start in it, over the iterations that
start in it. Self time leaves out a page move inside a tick, which is the
pager's. Layer: the executor (``core/executor.py``)."""
from chipbench import program_spans


def read(run):
    its = program_spans.in_window(run, "iteration")
    own = program_spans.in_window(run, "executor.pick", "executor.tick")
    if not its:
        return None
    return sum(program_spans.span_log(run).self_ns(own)) / len(its) / 1e6
