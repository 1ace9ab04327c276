"""Cut a recorded chip trace down to a small test fixture.

    python3 tests/bench/record_trace.py <trace dir or .xplane.pb> <out.json.gz>

Keeps every event that overlaps the first ``KEEP_S`` seconds of the
``bench/window`` span (the span itself cut to that length) and writes
them as JSON rows [plane, line, name, start_ns, end_ns].
"""
import gzip
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

KEEP_S = 0.25


def main(src: str, dst: str) -> None:
    from bench import trace as tr
    evs = tr.load(src)
    lo, hi = tr.span(evs, "bench/window")
    hi = min(hi, lo + KEEP_S * 1e9)
    rows = []
    for e in evs:
        if e.end > lo and e.start < hi:
            if e.name == "bench/window":
                e = e._replace(start=lo, end=hi)
            rows.append(list(e))
    with gzip.open(dst, "wt") as f:
        json.dump(rows, f)
    print(f"{len(rows)} events -> {dst}")


if __name__ == "__main__":
    main(*sys.argv[1:3])
