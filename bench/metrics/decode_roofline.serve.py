"""decode_roofline.serve: the least time the decode steps could take,
reading what they must from HBM, over their device time, in percent.

Requests served by one call at one exit form a decode group; it needs
as many steps as its longest request uses positions, and each step must
read the weights up to the exit, the LM head and the keys and values its
unfinished rows attend to (``shapes/decoder_step.step_bytes``). Device
time is that of the decode step programs (``jit_serve_step``) in the
traced window. Moves ``serve_tokens_per_s``."""
from bench import trace as tr
from bench.shapes import decoder_step


def read(ctx):
    ev = ctx["events"]
    window = tr.span(ev, "bench/window")
    if window is None or not ctx["served"]:
        return None
    seconds = tr.module_seconds(ev, ctx["module_prefix"], *window)
    if seconds <= 0:
        return None
    model = ctx["config"]["model"]
    groups = {}
    for req, toks, exit_layer, _, done in ctx["served"]:
        used = len(req.prompt) + len(toks) - 1
        groups.setdefault((done, exit_layer), []).append(used)
    need = 0.0
    for (_, exit_layer), used in groups.items():
        for p in range(max(used)):
            need += decoder_step.step_bytes(
                model, exit_layer, [p + 1 for u in used if p < u])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
