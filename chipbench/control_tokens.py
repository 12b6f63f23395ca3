"""The control of a token-table cell's comparison (``kind:
training_rounds_tokens``): the reference put in the program's place,
computed one step of precision below what the configuration states (float8
under a bfloat16 configuration), and the faults the cell can see, planted in
the reference's equations (``reference_moe_trunk.py``). Each has to come out
as not correct under the cell's limits; ``bfloat16``, the reference at the
stated precision, has to pass.

    python3 chipbench/control_tokens.py --workload st21b-ep4.b16 --seeds 11 12 13

runs on the chip at the cell's own size, holds every case to the cell's own
limits through ``check.verdict``, prints one JSON line per seed and exits
non-zero if a case came out wrong. The benchmark's own runs never run it.
One case lies on the chip at a time, and only the float32 reference's
result waits on the host beside it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import cells, check, control, corpus, corpus_tokens  # noqa: E402
from chipbench import reference_moe_trunk as reference  # noqa: E402

# case -> (precision, fault); what has to come out as not correct, and as correct
CASES = {
    "float8": ("float8", None), "bfloat16": ("bfloat16", None),
    "drop_last_choice": ("float32", "drop_last_choice"),
    "rotary_everywhere": ("float32", "rotary_everywhere"),
}
MUST_FAIL = ("float8", "drop_last_choice", "rotary_everywhere", "state_unchanged")
MUST_PASS = ("bfloat16",)


def readings(config: dict, traffic: dict, seed: int, cases=tuple(CASES)) -> dict:
    """{case: numbers} against the float32 reference, on plain batches at the
    cell's own shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    shapes, trunk = config["shapes"], corpus_tokens.trunk_of(config)
    corp = corpus.make_click_corpus(traffic, shapes, seed)
    tokens = jnp.asarray(corpus_tokens.make_token_table(traffic, shapes, trunk, seed), jnp.int32)
    # on the host, as the harness keeps them: the reference takes its one copy
    user0, news0 = jax.tree_util.tree_map(np.asarray, corpus_tokens.make_weights(shapes, trunk, seed))
    batches = control.plain_batches(corp, shapes, control.FOLLOWED_STEPS)
    follow = lambda **kw: reference.follow_steps(  # noqa: E731
        shapes, trunk, user0, news0, tokens, batches, control.LR, **kw)
    ref = follow()
    out = {"state_unchanged": check.compare_steps(
        control.state_unchanged({**ref, "losses": ref["losses"][:1].repeat(len(batches), 0)}), ref)["numbers"]}
    for name in cases:
        precision, fault = CASES[name]
        compared = check.compare_steps(control.as_program(follow(precision=precision, fault=fault)), ref)
        out[name] = dict(compared["numbers"])
        print(f"{name}, seed {seed}: worst leaves {compared['worst_leaf']}", file=sys.stderr, flush=True)
    return out


def judge(all_readings: dict, limits: dict) -> tuple[dict, list]:
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
    ap.add_argument("--cases", nargs="+", default=list(CASES), choices=list(CASES))
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    failed = False
    for seed in args.seeds:
        out = readings(cell["config"], cell["traffic"], seed, tuple(args.cases))
        verdicts, wrong = judge(out, cell["limits"])
        failed = failed or bool(wrong)
        print(json.dumps({"workload": args.workload, "seed": seed, "readings": out,
                          "verdicts": verdicts, "wrong": wrong}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
