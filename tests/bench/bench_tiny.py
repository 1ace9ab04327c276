"""A serving cell shrunk to a size the CPU runs in seconds."""
import argparse
import time

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=4,
                  vocab_size=512, initializer_range=0.3)


def tiny_cell(name: str, *, replay: bool = False):
    """``name`` shrunk: 2 layers, exits after 1 and 2, two batch slots.
    ``replay=True`` makes set-up find the decode shapes to warm by
    scheduling the window's calls ahead, instead of warming all."""
    from bench import manifest
    cell = manifest.resolve(name)
    cell["config"]["model"].update(TINY_MODEL)
    n = cell["config"]["model"]["num_hidden_layers"]
    cell["config"].update(exit_layers=[n // 2, n], batch_slots=2)
    mix = cell["traffic"]
    mix.update(clients=2, prompt_len=[3, 8], answer_len=[2, 6],
               check_requests=8)
    if replay:
        mix.update(warm_slots=1024)
        cell["driver"].ALL_PAIRS = 0
    return cell


def run_tiny(cell, seed=2**31 + 11, seconds=0.5, trace=0):
    from bench import run
    args = argparse.Namespace(workload=cell["cell"]["name"], seed=seed,
                              seconds=seconds, trace=trace)
    return run.run_cell(args, t_process=time.time(), require_tpu=False,
                        resolved=cell)
