"""sched_ms.serve: mean host time of the scheduling phase of a call, the
``serve/price`` span (the GRLE agent step, the MEC world step, the
metrics and telemetry updates, their host reads), in ms. None where the
program has no such span. Moves ``serve_tokens_per_s``."""
from bench import spans
from bench import trace as tr


def read(ctx):
    ev = ctx["events"]
    window = tr.span(ev, spans.WINDOW)
    if window is None:
        return None
    price = spans.host_spans(ev, "serve/price", *window)
    if not price:
        return None
    return sum(e - s for s, e in price) / len(price) * 1e-6
