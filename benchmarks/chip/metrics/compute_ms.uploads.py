"""Mean time per whole round of the window in client compute, its
read-backs included (the program's ``fed.compute_cohort`` spans), in
milliseconds."""
from program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "fed.compute_cohort")
