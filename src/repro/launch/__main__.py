"""Subcommand dispatch: ``python -m repro.launch <command> [args...]``.

Commands:
  sweep       sharded (scenario x method x seed) experiment grids
  pop         population training: vmapped PBT + scenario auto-curriculum
  serve       GRLE-scheduled early-exit LM serving driver
  serve-bench serving throughput: sync slot loop vs continuous batching
  train       LLM training-step driver
  dryrun      multi-pod compile dry-run
  profile     instrumented rollout: telemetry + compile/trace + JSONL log
  history     run-history trend tables + noise-aware regression verdicts

``python -m repro.launch.serve`` style module paths keep working; this
entry point just gives the drivers one front door.
"""
from __future__ import annotations

import sys


def main() -> None:
    commands = ("sweep", "pop", "serve", "serve-bench", "train", "dryrun",
                "profile", "history")
    if len(sys.argv) < 2 or sys.argv[1] in ("-h", "--help"):
        print(__doc__)
        raise SystemExit(0 if len(sys.argv) >= 2 else 2)
    cmd, argv = sys.argv[1], sys.argv[2:]
    if cmd not in commands:
        print(f"unknown command {cmd!r}; choose from {', '.join(commands)}")
        raise SystemExit(2)
    from repro.obs.compile import use_compile_cache
    use_compile_cache()
    if cmd == "sweep":
        from repro.launch.sweep import main as run
        run(argv)
        return
    if cmd == "pop":
        from repro.launch.pop import main as run
        run(argv)
        return
    if cmd == "profile":
        from repro.launch.profile import main as run
        run(argv)
        return
    if cmd == "history":
        from repro.launch.history import main as run
        run(argv)
        return
    if cmd == "serve-bench":
        from repro.launch.serve_bench import main as run
        run(argv)
        return
    # legacy drivers parse sys.argv directly
    sys.argv = [f"repro.launch.{cmd}"] + argv
    if cmd == "serve":
        from repro.launch.serve import main as run
    elif cmd == "train":
        from repro.launch.train import main as run
    else:
        from repro.launch.dryrun import main as run
    run()


if __name__ == "__main__":
    main()
