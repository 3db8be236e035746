"""Mean time per whole round of the window in which the host waits on
a device result: client compute's read-backs, the apply's wait, the
evaluation's ``float`` (the program's ``fed.device_wait`` spans), in
milliseconds."""
from program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "fed.device_wait")
