"""Mean time per whole round of the window in the cohort draw (the
program's ``fed.sample_cohort`` spans), in milliseconds."""
from program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "fed.sample_cohort")
