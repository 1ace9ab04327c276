"""idle_decode.serve: share of the traced window in which no op runs on
the device while the host is inside ``serve/decode``, the host-driven
decode loop of one exit group, in percent (exact interval intersection).
None where the program has no such span. Moves ``serve_tokens_per_s``."""
from bench import spans


def read(ctx):
    return spans.idle_inside(ctx["events"], "serve/decode")
