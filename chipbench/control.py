"""The control of the comparison: the reference put in the program's place,
computed one step of precision below what the configurations state (float8
under a bfloat16 configuration: e4m3 operands, e5m2 cotangents), and the faults a training cell can
have, planted in the reference. Each has to come out as not correct under
the cell's limits; the readings set the limits' upper ends (``PERF.md``).

    python3 chipbench/control.py --workload fed8.b64 --seeds 11 12 13

runs on the chip at the cell's own size, holds every case to the cell's own
limits (``chipbench/limits/<cell>.json``) through ``check.verdict``, prints
one JSON line per seed with each case's ``correct``, and exits non-zero if
the control or a fault came out correct. The benchmark's own runs never run
it. ``bfloat16`` is read too: the reference at the stated precision, a
second witness for the program's own readings, which has to pass.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import cells, check, corpus, reference  # noqa: E402

LR = 5e-5
FOLLOWED_STEPS = 3


def plain_batches(corp: dict, shapes: dict, steps: int) -> list:
    """The first ``steps`` batches dealt in corpus order, four negatives from
    the head of each pool: rows that all differ, at the cell's own shapes."""
    k, b = shapes["clients"], shapes["batch_per_client"]
    n_neg = shapes["candidates"] - 1
    out = []
    for i in range(steps):
        rows = np.arange(i * k * b, (i + 1) * k * b).reshape(k, b)
        cand = np.concatenate([corp["pos"][rows][..., None], corp["negs"][rows][..., :n_neg]], -1)
        out.append({"candidates": cand.astype(np.int32), "history": corp["history"][rows].astype(np.int32)})
    return out


def as_program(result: dict) -> dict:
    """A ``follow_steps`` result in the form the harness records the program
    in (Adam's first moment instead of the gradient)."""
    import jax

    scale = lambda t: jax.tree_util.tree_map(lambda x: x * (1.0 - check.ADAM_B1), t)  # noqa: E731
    return {"losses": result["losses"], "deltas": result["deltas"],
            "first_mu": [scale(g) for g in result["first_grads"]]}


def state_unchanged(still: dict) -> dict:
    """A step that returns its state as it got it: no moment, no change, and
    every loss that of the first weights. ``still``: the reference followed
    with a learning rate of nought."""
    import jax

    zero = lambda t: jax.tree_util.tree_map(np.zeros_like, t)  # noqa: E731
    return {"losses": still["losses"], "deltas": [zero(d) for d in still["deltas"]],
            "first_mu": [zero(g) for g in still["first_grads"]]}


def readings(shapes: dict, traffic: dict, seed: int, dtype="bfloat16",
             witness: bool = False) -> dict:
    """{name: numbers} of the control (``float8``), of the stated precision
    (``bfloat16``) and of each fault, against the float32 reference; with
    ``witness`` also of bfloat16 arithmetic throughout (``bfloat16_all``)."""
    import jax.numpy as jnp

    corp = corpus.make_click_corpus(traffic, shapes, seed)
    table = corpus.make_token_states(traffic, shapes, seed, jnp.dtype(dtype), corp["popular_rows"])
    user0, news0 = corpus.make_weights(shapes, seed)
    batches = plain_batches(corp, shapes, FOLLOWED_STEPS)
    follow = lambda lr=LR, **kw: reference.follow_steps(shapes, user0, news0, table, batches, lr, **kw)  # noqa: E731
    ref = follow()
    half = slice(0, shapes["batch_per_client"] // 2)
    cases = {
        "float8": as_program(follow(precision="float8")),
        "bfloat16": as_program(follow(precision="bfloat16")),
        "half_batch": as_program(follow(keep=half)),
        "state_unchanged": state_unchanged(follow(lr=0.0)),
    }
    if witness:
        cases["bfloat16_all"] = as_program(follow(precision="bfloat16_all"))
    out = {}
    for name, prog in cases.items():
        compared = check.compare_steps(prog, ref)
        out[name] = dict(compared["numbers"])
        if name == "bfloat16_all":
            print(f"bfloat16_all, seed {seed}: worst leaves {compared['worst_leaf']}\n"
                  + check.leaf_table(compared), file=sys.stderr, flush=True)
    if shapes["clients"] > 1:
        # the round-end sync's faults, on the clients' own changed parameters
        before = ref["deltas"]
        out["sync_broadcast"] = {"sync_gap": check.sync_gap(before, [before[0]] * len(before))}
        out["sync_left_out"] = {"sync_gap": check.sync_gap(before, before)}
    return out


# what has to come out as not correct, and what as correct
MUST_FAIL = ("float8", "half_batch", "state_unchanged", "sync_broadcast", "sync_left_out")
MUST_PASS = ("bfloat16",)


def judge(all_readings: dict, limits: dict) -> tuple[dict, list]:
    """Each case under the cell's limits (those of its numbers that the case
    reads): {case: {"correct", "over"}} and the cases that came out wrong."""
    verdicts, wrong = {}, []
    for case, numbers in all_readings.items():
        held = {k: v for k, v in limits.items() if k in numbers}
        ok, compared = check.verdict(numbers, held)
        verdicts[case] = {"correct": ok,
                          "over": sorted(k for k, c in compared.items() if not c["value"] <= c["limit"])}
        if (case in MUST_FAIL and ok) or (case in MUST_PASS and not ok):
            wrong.append(case)
    return verdicts, wrong


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--witness", action="store_true",
                    help="also read bfloat16 arithmetic throughout (printed, not judged)")
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    failed = False
    for seed in args.seeds:
        out = readings(cell["config"]["shapes"], cell["traffic"], seed, witness=args.witness)
        verdicts, wrong = judge(out, cell["limits"])
        failed = failed or bool(wrong)
        print(json.dumps({"workload": args.workload, "seed": seed, "readings": out,
                          "verdicts": verdicts, "wrong": wrong}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
