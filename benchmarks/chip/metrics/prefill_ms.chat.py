"""prefill_ms.chat: mean device time of one prefill program in the trace.
Matched by the jit name the serving engine gives it today: a rename
leaves the metric out of the line rather than wrong."""
from readers import program_ms

PROGRAMS = ("prefill_step",)


def read(run):
    return program_ms(run, PROGRAMS)
