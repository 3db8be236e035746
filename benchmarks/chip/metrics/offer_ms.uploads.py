"""Mean time per whole round of the window in offering the uploads to
the aggregator (the program's ``fed.offer_uploads`` spans), in
milliseconds."""
from program_spans import stage_ms


def read(ctx):
    return stage_ms(ctx, "fed.offer_uploads")
