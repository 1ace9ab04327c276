"""idle_share.train: share of the traced window in which no operation ran
on the device, in percent, while the window chains training episodes.
Moves ``train_slots_per_s``."""
from bench import trace as tr


def read(ctx):
    return tr.idle_share(ctx["events"])
