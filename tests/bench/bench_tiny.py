"""Benchmark cells shrunk to a size the CPU runs in seconds."""
import argparse
import contextlib
import copy
import functools
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


SEED = 2**31 + 11


def run_tiny(cell, seed=SEED, seconds=0.5, trace=0):
    from bench import run
    args = argparse.Namespace(workload=cell["cell"]["name"], seed=seed,
                              seconds=seconds, trace=trace)
    return run.run_cell(args, t_process=time.time(), require_tpu=False,
                        resolved=cell)


TINY_TRAIN = dict(n_devices=4)
TINY_AGENT = dict(hidden=[16, 8], candidates=37, n_random=4, replay=16,
                  minibatch=8, train_every=5)


@functools.lru_cache(maxsize=None)
def train_cell():
    """``grle_paper.train64`` at full size, resolved by ``manifest.resolve``
    from ``BENCHMARK.json`` with the cell's entries from
    ``bench/pending/``, where they wait until chip readings set its limits
    and bound. Shared within a process: copy before changing."""
    from bench import manifest
    m = manifest.load_manifest()
    pending = manifest.load_json(manifest.BENCH_DIR / "pending"
                                 / "grle_paper.train64.json")
    for key, entries in pending.items():
        m[key] = m[key] + entries
    return manifest.resolve("grle_paper.train64", m)


def tiny_train_cell():
    """``grle_paper.train64`` shrunk: 4 devices, hidden (16, 8), replay
    16, minibatch 8, a train step every 5 slots, 2 fleets, 15-slot
    episodes (three steps each). The pieces resolve once per process, so
    every tiny run shares the reference's compiled slot."""
    cell = dict(train_cell())
    cell["config"] = copy.deepcopy(cell["config"])
    cell["config"]["mec"].update(TINY_TRAIN)
    cell["config"]["agent"].update(TINY_AGENT)
    cell["traffic"] = dict(cell["traffic"], fleets=2, episode_slots=15)
    return cell


def train_ctx(cell, seed=SEED):
    """The driver's context for ``cell`` without a run: for the control."""
    return dict(config=cell["config"], traffic=cell["traffic"],
                reference=cell["reference"], seed=seed)


@contextlib.contextmanager
def quick_compiles():
    """XLA's optimisation passes off while the tiny training runs compile
    their episode and the reference's slot: the tests check what the
    programs compute, and compile in half the time."""
    import jax
    old = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", True)
    try:
        yield
    finally:
        jax.config.update("jax_disable_most_optimizations", old)
