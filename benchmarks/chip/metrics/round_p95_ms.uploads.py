"""95th percentile of the whole rounds of the window, each the
program's ``fed.round`` span around one scheduler round, in
milliseconds."""
from program_spans import round_p95_ms


def read(ctx):
    return round_p95_ms(ctx)
