"""Read the training check's numbers with each planted fault, on the chip
at the cell's own size.

    python3 tests/bench/read_faults.py --seeds 1,2,3 \\
        [--faults half_batch,wrong_reward]

It plants every fault of ``train_faults.FAULTS`` into the program once
(one compile), and for each fault named and each seed switches that
fault on, runs ``grle_paper.train64`` as ``bench.run`` does with a
window of one episode, and prints one JSON line with every number of the
check beside its limit. A number's readings under the faults that it
has to catch set the upper end of its limit, beside the control's
(``bench.control``).
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1])]


def main(argv=None) -> None:
    import pytest
    import train_faults
    from bench import run
    from bench_tiny import train_cell
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(train_faults.FAULTS))
    args = ap.parse_args(argv)
    cell = train_cell()
    sys.path.insert(0, str(run.ROOT / "src"))
    jax = run.setup_jax()
    devs = run.devices(jax, cell["cell"]["chips"])
    mp = pytest.MonkeyPatch()
    train_faults.plant(mp)
    try:
        for name in args.faults.split(","):
            catcher = train_faults.FAULTS[name][1]
            for seed in [int(s) for s in args.seeds.split(",")]:
                train_faults.switch(name)
                with jax.default_device(devs[0]):
                    res = cell["driver"].run(dict(
                        config=cell["config"], traffic=cell["traffic"],
                        reference=cell["reference"], seed=seed, seconds=0.1,
                        trace=False, trace_dir=None, t_process=time.time(),
                        peaks=None))
                print(json.dumps({"fault": name, "catcher": catcher,
                                  "seed": seed, "correct": res["correct"],
                                  "checks": res["checks"]}), flush=True)
    finally:
        train_faults.switch(None)
        mp.undo()


if __name__ == "__main__":
    main()
