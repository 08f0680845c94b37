"""Median, over the switches in the traced part of the window, of the
device's idle from the last operation before the incoming job's
``iteration`` span to its first operation: what a switch between two
resident jobs costs the chip. The program's spans are put on the trace's
clock by ``program_spans``. Layer: the executor (``core/executor.py``)."""
import statistics

from chipbench import program_spans


def read(run):
    gaps = program_spans.switch_gaps(run)
    return statistics.median(g for _, g in gaps) / 1e6 if gaps else None
