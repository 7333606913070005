"""The control of a cell's comparison, on the chip at the cell's size.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 10

For each seed it makes one run of the cell (``cell.run``: set-up, a
window at the cell's own load, the comparison), then puts the plain
reference in the program's place, computed one precision below the
configuration's float32: bfloat16 (``reference.to_bfloat16`` on every
restricted-solve result and input, the full-width passes on a bfloat16
copy of the design), one answer for every (response, lam) pair the run
served. Those answers go through the same comparison (``correct.judge``)
with the cell's own limits. One JSON line per seed gives the program's
numbers and the control's, each beside its limit, and whether each came
out correct. It exits 0 only when every control came out not correct
and every program run correct. The benchmark's own runs never run this.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

# restricted-solve iterations of the control: its bfloat16 iterates stall
# long before this, so the cap only bounds its time
CONTROL_ITERS = 3000


def judge_control(keep: dict):
    """(correct, checks) of the bfloat16 reference in the program's place,
    from what one run of the cell kept (``cell.run(..., keep=)``)."""
    import jax.numpy as jnp
    from bench import correct, reference
    X, Y, loss, refs = keep["X"], keep["Y"], keep["loss"], keep["refs"]
    answers = []
    for (r, lam) in sorted(refs):
        sol = reference.solve(X, Y[r], lam, loss, q=reference.to_bfloat16,
                              wide_dtype=jnp.bfloat16,
                              fista_iters=CONTROL_ITERS)
        answers.append((r, lam, sol.dense(X.shape[1]), False))
    ok, checks, _ = correct.judge(X, Y, loss, answers, 0, keep["limits"],
                                  refs=refs)
    return ok, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import cell
    sound = True
    for seed in args.seeds:
        keep: dict = {}
        out = cell.run(args.workload, seed, args.seconds, False, keep=keep)
        ctl_ok, ctl_checks = judge_control(keep)
        for k, c in ctl_checks.items():
            print(f"control {k}: {c['value']!r} limit {c['limit']!r}",
                  file=sys.stderr)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "pairs": len(keep["refs"]),
                          "program_correct": out["correct"],
                          "program": out["checks"],
                          "control_correct": ctl_ok,
                          "control": ctl_checks}), flush=True)
        sound = sound and out["correct"] and not ctl_ok
        keep.clear()
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
