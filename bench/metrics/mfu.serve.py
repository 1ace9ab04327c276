"""mfu.serve: model operations of the positions requests really used,
over the traced window, over the chip's bf16 peak, in percent.

Each finished request uses positions 0 .. prompt + answer - 2 at its own
exit depth; padding positions of a decode group are not counted. Moves
``serve_tokens_per_s``."""
from bench import trace as tr
from bench.shapes import decoder_step


def read(ctx):
    ev = ctx["events"]
    window = tr.span(ev, "bench/window")
    if window is None or not ctx["served"] or not tr.device_planes(ev):
        return None
    model = ctx["config"]["model"]
    flops = 0.0
    for req, toks, exit_layer, *_ in ctx["served"]:
        used = len(req.prompt) + len(toks) - 1
        flops += sum(decoder_step.flops(model, exit_layer, p + 1)
                     for p in range(used))
    seconds = (window[1] - window[0]) * 1e-9
    return 100.0 * flops / (seconds * ctx["peaks"]["flops_bf16"])
