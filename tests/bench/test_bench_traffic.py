"""The traffic generator: the same sizes in the same order for every
seed, which draws only the prompt tokens."""
import itertools

import numpy as np
import pytest

from bench import manifest, traffic

# the request mixes; a training job's mix (fleets, episode length) sends
# no requests
MIXES = sorted(p.stem for p in (manifest.BENCH_DIR / "traffic").glob("*.json")
               if "block" in manifest.load_json(p))


def take(mix, seed, n, vocab=1000):
    return list(itertools.islice(traffic.stream(mix, seed, vocab), n))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes(name):
    mix = manifest.load_json(manifest.BENCH_DIR / "traffic" / f"{name}.json")
    n = 2 * mix["block"]
    a, b = take(mix, 1, n), take(mix, 2**31 + 5, n)
    sizes = lambda rs: sorted((len(r.prompt), r.answer_len) for r in rs)
    assert sizes(a) == sizes(b)
    assert sizes(a[: mix["block"]]) == sizes(a[mix["block"]:])
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.answer_len for r in a] == [r.answer_len for r in b]
    for r in a:
        assert mix["prompt_len"][0] <= len(r.prompt) <= mix["prompt_len"][1]
        assert mix["answer_len"][0] <= r.answer_len <= mix["answer_len"][1]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = manifest.load_json(manifest.BENCH_DIR / "traffic" / f"{name}.json")
    a, b = take(mix, 77, 40), take(mix, 77, 40)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.answer_len == y.answer_len
    assert all(0 <= t < 1000 for r in a for t in r.prompt)


def test_uniform_grid_spans_the_range():
    g = traffic._uniform_grid(16, 64, 49)
    assert g.min() == 16 and g.max() == 64 and len(set(g)) == 49


@pytest.mark.parametrize("name", MIXES)
def test_seed_draws_only_the_tokens(name):
    mix = manifest.load_json(manifest.BENCH_DIR / "traffic" / f"{name}.json")
    a, b = take(mix, 5, 40), take(mix, 2**31 + 9, 40)
    assert [(len(r.prompt), r.answer_len) for r in a] == list(
        itertools.islice(traffic.sizes(mix), 40))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
