"""Tests of the benchmark's own code. Run with

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests

They are not part of the repo's tier-1 command (``pytest tests/``). The
harness itself refuses any device but a TPU; only these tests drive it on
the CPU, at a tiny size, and no number from them is a device metric."""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

BENCH = ROOT / "chipbench"

TINY_SHAPES = {
    "clients": 2, "batch_per_client": 4, "candidates": 5, "history": 6,
    "title_len": 8, "bert_hidden": 32, "attn_hidden": 16, "news_dim": 32,
    "heads": 4, "head_dim": 8, "query_dim": 16, "catalog_rows": 256,
}
TINY_OVERRIDES = [
    "fed.num_clients=2", "fed.strategy=param_avg", "data.batch_size=4",
    "data.dataset=synthetic", "data.max_his_len=6", "data.max_title_len=8",
    "model.text_encoder_mode=head", "model.dtype=bfloat16", "model.dropout_rate=0.0",
    "model.bert_hidden=32", "model.news_dim=32", "model.num_heads=4",
    "model.head_dim=8", "model.query_dim=16",
    "fed.rounds=1000000", "train.eval_every=1000000", "train.save_every=1000000",
    "train.snapshot_dir=", "train.resume=false",
]
TINY_TRAFFIC = {
    "kind": "training_rounds", "num_news": 256, "samples_per_round": 32,
    "popularity": {"law": "zipf_mandelbrot", "exponent": 1.0, "offset": 5},
    "popular_frac": 0.2, "history_len": 6, "negative_pool": 8, "traced_rounds": 1,
}


def write_tiny_benchmark(root: Path, limits: dict, clients: int = 2) -> str:
    """A throw-away configuration, traffic mix and cell in ``root``, added as
    a later PR would add them: new files and new entries, nothing edited."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shapes = dict(TINY_SHAPES, clients=clients)
    overrides = list(TINY_OVERRIDES)
    if clients == 1:
        overrides = [o.replace("fed.num_clients=2", "fed.num_clients=1")
                      .replace("fed.strategy=param_avg", "fed.strategy=grad_avg")
                     for o in overrides]
    bdir = root / "chipbench"
    for sub in ("configs", "traffic", "limits"):
        (bdir / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", bdir / "metrics", dirs_exist_ok=True)
    (bdir / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "source": "test", "shapes": shapes, "overrides": overrides,
         "device_modules": {"train_step": "jit_sharded_step", "param_sync": "jit_sharded_sync"}}))
    (bdir / "traffic" / "tinyrounds.json").write_text(json.dumps(TINY_TRAFFIC))
    (bdir / "limits" / "tiny.cell.json").write_text(json.dumps({"limits": limits}))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny", "traffic": "tinyrounds",
                               "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return "tiny.cell"
