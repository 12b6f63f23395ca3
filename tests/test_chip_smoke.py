"""``chip_smoke.py`` on the CPU at tiny widths.

The script's phases are functions that take the arguments a user would
type, so the test hands them a small model through ``--set`` and drives the
same code the chip runs: the trainer phase, the server phase and the
``--chips 4`` comparison (over four of the eight fake CPU devices). The
script itself has no option or environment variable for this.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402

TINY = [
    "--set", "data.max_his_len=10", "--set", "data.max_title_len=8",
    "--set", "model.bert_hidden=32", "--set", "model.news_dim=32",
    "--set", "model.num_heads=4", "--set", "model.head_dim=8",
    "--set", "model.query_dim=16",
    "--set", "optim.user_lr=3e-3", "--set", "optim.news_lr=3e-3",
]


@pytest.fixture()
def work_dir(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "WORK_DIR", tmp_path / "work")
    return tmp_path / "work"


def _tiny_train_argv(clients: int, samples: int) -> list[str]:
    argv = chip_smoke.train_argv(300, clients, samples)
    argv[1] = "16"  # batch per client: the positional a user would change
    return argv + TINY


def test_trainer_phase(work_dir, capsys):
    out = chip_smoke.train_phase(_tiny_train_argv(2, 256))
    assert out["catalog_rows"] == 300 and out["catalog_dtype"] == "bfloat16"
    assert out["clients"] == 2 and out["steps_per_round"] == 8
    assert len(out["round_losses"]) == 2
    assert out["round_losses"][-1] < out["round_losses"][0]
    assert 0.5 < out["val_auc"] <= 1.0  # the synthetic signal is learnable
    assert len(out["later_round_seconds"]) == 1
    assert (work_dir / "train").is_dir()  # the end-of-run snapshot
    # the table's at-rest check: committed once, row-major, no copy of it
    assert out["table_commit"]["set"] == "(0, 1, 2)"
    assert "major_to_minor=(0, 1, 2)" in out["step_states_table_layout"]
    printed = capsys.readouterr().out
    assert "train.final_loss:" in printed and "train.table_format:" in printed


def test_a_copy_of_the_whole_table_in_the_step_is_found():
    """The two ``copy`` lines are the parent's (ledger, PR 26: 16.33 ms a
    step); the gather's result has another shape and is no such copy."""
    import jax.numpy as jnp

    table = jnp.zeros((64, 5, 8), jnp.bfloat16)
    hlo = """
  %table.1 = bf16[64,5,8]{2,0,1:T(8,128)(2,1)} parameter(57), sharding={replicated}
  %copy.363 = bf16[64,5,8]{2,1,0:T(8,128)(2,1)} copy(%table.1), sharding={replicated}
  %fusion = bf16[28,5,8]{2,1,0:T(8,128)(2,1)} fusion(%copy.363, %copy-done.35), kind=kCustom
  %copy.445 = bf16[28,5,8]{0,2,1:T(8,128)(2,1)} copy(%fusion.2)
  %copy.9 = bf16[64,5,8]{2,1,0} copy(bf16[64,5,8]{2,0,1} %p)
"""
    found = chip_smoke.table_copies(hlo, table)
    assert len(found) == 2 and found[0].startswith("%copy.363")
    assert chip_smoke.table_copies(hlo, jnp.zeros((64, 5, 8), jnp.float32)) == []


def test_server_phase(capsys):
    out = chip_smoke.serve_phase(
        ["--synthetic", "500", *TINY], n_requests=24
    )
    assert out["requests_answered"] == 24
    assert out["generations_served"] == [0, 1] and out["swap_count"] == 1
    assert sorted(out["warmup_seconds_per_bucket"]) == [1, 8, 32, 128]
    printed = capsys.readouterr().out
    assert "[serve] listening on" in printed
    assert "[serve] signal received; draining" in printed  # clean shutdown


def test_four_chip_comparison(work_dir):
    out = chip_smoke.four_chip_phase(
        _tiny_train_argv(2, 128), rtol=1e-5
    )
    assert out["clients"] == out["devices_holding_client_state"] == 2
    assert max(out["loss_rel_diff"]) <= 1e-5  # float32 CPU: tight


def test_main_refuses_a_platform_that_is_not_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    captured = capsys.readouterr()
    assert "needs a TPU" in captured.err and "'cpu'" in captured.err
    assert captured.out == ""  # no result line, nothing that parses as one


def test_result_line_has_exactly_the_contract_keys():
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    )
    parsed = json.loads(line)
    assert set(parsed) == {"ok", "device"} and parsed["ok"] is True
    assert parsed["device"] == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1
    }
    assert "\n" not in line
