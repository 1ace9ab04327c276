"""mfu.train: model operations of the traced episodes, over the traced
window, over the chip's bf16 peak, in percent.

Each episode runs the actor's forward once per fleet and slot and, at
each train step, a forward and a backward over the minibatch
(``shapes/grle.episode_flops``): the whole step's share of the chip,
which bounds every kernel's. Moves ``train_slots_per_s``."""
from bench import trace as tr
from bench.shapes import grle


def read(ctx):
    ev = ctx["events"]
    window = tr.span(ev, "bench/window")
    if window is None or not ctx["episodes"] or not tr.device_planes(ev):
        return None
    mix = ctx["traffic"]
    flops = ctx["episodes"] * grle.episode_flops(
        ctx["config"], mix["fleets"], mix["episode_slots"])
    seconds = (window[1] - window[0]) * 1e-9
    return 100.0 * flops / (seconds * ctx["peaks"]["flops_bf16"])
