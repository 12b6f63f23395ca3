"""The control of the window trunk's cell (``kind:
training_rounds_tokens_window``), in the shape of ``control_latent.py``: the
reference put in the program's place, computed one step of precision below
what the configuration states (float8 under a bfloat16 configuration), and
the faults the cell can see: a step that sees half its batch, a state left
unchanged, and five planted in the reference's equations
(``reference_window_trunk.py``): the window ignored in the window layers, the
whole head rotated in the full layers, the gate set to 1, the full layers'
heads grouped as the window layers' are, the 8th choice dropped. Each of
``MUST_FAIL`` has to come out as not correct under the cell's limits.

    python3 chipbench/control_window.py --workload laguna33b-ep8.b1 --seeds 11 12 13

runs on the chip at the cell's own size, holds every case to the cell's own
limits through ``check.verdict``, prints one JSON line per seed and exits
non-zero if a case came out wrong. The benchmark's own runs never run it.
One case lies on the chip at a time, and only the float32 reference's
result waits on the host beside it. ``half_batch`` needs a batch of two or
more: at the cell's B = 1 the first half is no sample at all, so the case is
the step that sees its history's first half (25 of 50 clicks).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import cells, check, control, corpus, corpus_window  # noqa: E402
from chipbench import reference_window_trunk as reference  # noqa: E402
from chipbench.control_tokens import judge as _judge  # noqa: E402
from chipbench.harness_training_rounds_tokens_latent import routed_numbers  # noqa: E402

# case -> what the reference is followed with in the program's place:
# ``precision``, ``fault`` (``reference_window_trunk.FAULTS``), ``half`` (the
# step sees the first half of its clicks)
CASES = {
    "float8": {"precision": "float8"}, "bfloat16": {"precision": "bfloat16"},
    "half_batch": {"half": True},
    "window_ignored": {"fault": "window_ignored"},
    "whole_head_rotary": {"fault": "whole_head_rotary"},
    "gate_one": {"fault": "gate_one"},
    "heads_regrouped": {"fault": "heads_regrouped"},
    "drop_last_choice": {"fault": "drop_last_choice"},
}
# ``bfloat16`` is a witness that rounds otherwise than the program: read and
# printed, not judged
MUST_FAIL = ("float8", "half_batch", "window_ignored", "whole_head_rotary", "gate_one",
             "heads_regrouped", "drop_last_choice", "state_unchanged")
MUST_PASS = ()
BLOCK_ROWS = 1


def readings(config: dict, traffic: dict, seeds, cases=tuple(CASES), block_rows: int = BLOCK_ROWS) -> dict:
    """{seed: {case: numbers}} against the float32 reference, on plain batches
    at the cell's own shapes. Case by case over all the seeds, so that a
    case's two programs are compiled once (two minutes) and lie on the chip
    alone; the float32 reference's leaf norms wait on the host meanwhile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    shapes, trunk = config["shapes"], corpus_window.trunk_of(config)

    def follow(seed, step, half=False):
        corp = corpus.make_click_corpus(traffic, shapes, seed)
        tokens = jnp.asarray(corpus_window.make_token_table(traffic, shapes, trunk, seed), jnp.int32)
        # on the host, as the harness keeps them: the reference takes its one copy
        user0, news0 = jax.tree_util.tree_map(np.asarray, corpus_window.make_weights(shapes, trunk, seed))
        batches = control.plain_batches(corp, shapes, control.FOLLOWED_STEPS)
        if half:
            # B = 1: half a batch is half its clicks (the other half's slots
            # read the padding row, as a shorter history's do)
            cut = shapes["history"] // 2
            batches = [{**b, "history": np.concatenate(
                [b["history"][..., :cut], np.zeros_like(b["history"][..., cut:])], axis=-1)} for b in batches]
        return reference.follow_steps(shapes, trunk, user0, news0, tokens, batches, control.LR, step=step)

    def norms(result):
        """The result with every leaf replaced by its norm: all the comparison
        reads of a tree, and 2.8 GB a tree less to keep on the host."""
        norm = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda x: np.array([np.linalg.norm(np.asarray(x, np.float64))]), tree)
        return {"losses": result["losses"], "first_grads": [norm(g) for g in result["first_grads"]],
                "deltas": [norm(d) for d in result["deltas"]]}

    def numbers(program, ref):
        compared = check.compare_steps(program, ref)
        return {**compared["numbers"], **routed_numbers(compared)}, compared["worst_leaf"]

    sound = reference.ReferenceStep(shapes, trunk, "float32", None, block_rows)
    refs = {seed: norms(follow(seed, sound)) for seed in seeds}
    out = {seed: {"state_unchanged": numbers(control.state_unchanged(
        {**ref, "losses": ref["losses"][:1].repeat(control.FOLLOWED_STEPS, 0)}), ref)[0]}
        for seed, ref in refs.items()}
    for name in cases:
        case = CASES[name]
        # the half batch is the sound step on other rows: its compiled blocks again
        step = sound if case.get("half") else reference.ReferenceStep(
            shapes, trunk, case.get("precision", "float32"), case.get("fault"), block_rows)
        for seed in seeds:
            result = norms(follow(seed, step, bool(case.get("half"))))
            out[seed][name], worst = numbers(control.as_program(result), refs[seed])
            print(f"{name}, seed {seed}: {json.dumps(out[seed][name])}; worst leaves {worst}",
                  file=sys.stderr, flush=True)
    return out


def judge(all_readings: dict, limits: dict) -> tuple[dict, list]:
    """``control_tokens.judge`` with this cell's lists of what must fail and pass."""
    verdicts, _ = _judge(all_readings, limits)
    wrong = [case for case, v in verdicts.items()
             if (case in MUST_FAIL and v["correct"]) or (case in MUST_PASS and not v["correct"])]
    return verdicts, wrong


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cases", nargs="+", default=list(CASES), choices=list(CASES))
    args = ap.parse_args(argv)
    cell = cells.load_cell(ROOT, args.workload)
    failed = False
    out = readings(cell["config"], cell["traffic"], args.seeds, tuple(args.cases))
    for seed in args.seeds:
        verdicts, wrong = judge(out[seed], cell["limits"])
        failed = failed or bool(wrong)
        print(json.dumps({"workload": args.workload, "seed": seed, "readings": out[seed],
                          "verdicts": verdicts, "wrong": wrong}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
