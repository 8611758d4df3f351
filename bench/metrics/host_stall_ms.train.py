"""host_stall_ms.train: milliseconds a traced step in which the device sat
idle while the host was outside the trainer's ``train.wait`` span: the time
the chip waits on the host (``bench.program_trace.host_stall_ms``).

Idle time under ``train.wait`` is the step program's own (the host is
blocked on it), so it is left out.  Nothing to read (no program trace, no
program span): None.
"""
from bench import program_trace


def read(ctx):
    pt = ctx["program"]
    return None if pt is None else program_trace.host_stall_ms(pt)
