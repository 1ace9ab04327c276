"""idle_share.serve: share of the traced window in which no operation ran
on the device, in percent, where throughput is the end-to-end metric.
Moves ``serve_tokens_per_s``."""
from bench import trace as tr


def read(ctx):
    return tr.idle_share(ctx["events"])
