"""host_syncs.serve: blocking device->host reads per call, the
``serve/pull`` spans per ``bench/serve_slot`` span in the traced window.
None where the program has no such span. Moves ``serve_tokens_per_s``."""
from bench import spans
from bench import trace as tr


def read(ctx):
    ev = ctx["events"]
    window = tr.span(ev, spans.WINDOW)
    if window is None:
        return None
    pulls = spans.host_spans(ev, "serve/pull", *window)
    calls = spans.host_spans(ev, "bench/serve_slot", *window)
    if not pulls or not calls:
        return None
    return len(pulls) / len(calls)
