"""gcn_roofline.train: the least time the actor's kernels could take over
the device time they took, in percent.

For every ``gcn_agg`` and ``edge_score`` call of the traced episodes
(``shapes/grle.episode_kernel_calls``) the least time is the larger of
its operations over the bf16 peak and its bytes over the HBM bandwidth;
the device time is that of the ops named after the two kernels in the
traced window. None where no such op ran. Moves ``train_slots_per_s``."""
from bench import trace as tr
from bench.shapes import grle

KERNELS = ("gcn_agg", "edge_score")


def kernel_seconds(ev, lo, hi) -> float:
    """Device time of ops named after the kernels, per device plane."""
    planes = tr.device_planes(ev)
    total = sum(min(e.end, hi) - max(e.start, lo) for e in ev
                if e.plane in planes and e.line == tr.OPS_LINE
                and e.name.startswith(KERNELS) and e.end > lo
                and e.start < hi)
    return total / max(len(planes), 1) * 1e-9


def read(ctx):
    ev = ctx["events"]
    window = tr.span(ev, "bench/window")
    if window is None or not ctx["episodes"]:
        return None
    seconds = kernel_seconds(ev, *window)
    if seconds <= 0:
        return None
    mix, peaks = ctx["traffic"], ctx["peaks"]
    calls = grle.episode_kernel_calls(ctx["config"], mix["fleets"],
                                      mix["episode_slots"])
    least = sum(max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
                for _, f, b in calls)
    return 100.0 * ctx["episodes"] * least / seconds
