"""Benchmark of the certified LASSO service on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. ``--workload`` names an entry of
``workloads`` in ``BENCHMARK.json``; see ``bench/cell.py`` for what a run
does. The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``breakdown`` with ``--trace 1``) and ``checks`` last: each number the
comparison judged beside its limit. The same numbers are the last lines
of standard error.

Exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, or when the system under test (``src/repro``) is
not in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import cell

    try:
        out = cell.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)
    except cell.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2
    except FileNotFoundError as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
