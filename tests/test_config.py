"""The config schema's contract with files written by older runs.

``to_dict`` writes every field, so a field deleted from the schema is in
every snapshot ``config.json`` (``cli/recommend.py``) and flight-recorder
manifest (``cli/obs.py``) written before it went. ``from_dict`` must keep
loading those (``config.py: _REMOVED_KEYS``), while a key that never was a
field, and an override that names a removed one, fail fast.
"""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from fedrec_tpu.config import _REMOVED_KEYS, ExperimentConfig

OLD_CONFIGS = Path(__file__).parent / "fixtures" / "old_configs"


@pytest.mark.parametrize("key,value", [
    ("train.total_epochs", 7),
    ("train.log_every", 50),
    ("data.unique_news_cap", 1234),           # removed by PR 30
    ("data.unique_news_cap_buckets", "512,1024"),
    ("train.scan_steps", 4),                  # removed with the scan forms
    ("train.rounds_per_scan", 2),
])
def test_from_dict_loads_a_removed_key(key, value):
    """A dict that holds a removed field at a non-default value loads, and
    the fields that remain take their values from it."""
    assert key in _REMOVED_KEYS
    section, name = key.split(".")
    d = ExperimentConfig().to_dict()
    assert name not in d[section]
    d[section][name] = value
    d["fed"]["num_clients"] = 16
    cfg = ExperimentConfig.from_dict(d)
    assert cfg.fed.num_clients == 16
    assert not hasattr(getattr(cfg, section), name)


@pytest.mark.parametrize("name,removed", [
    # ExperimentConfig().to_json() at PR 29's commit, with
    # data.unique_news_cap=1234, train.scan_steps=4, train.rounds_per_scan=2
    ("pr29.json", ("data.unique_news_cap", "data.unique_news_cap_buckets",
                   "train.scan_steps", "train.rounds_per_scan")),
    # the same at PR 30's commit, which had dropped the two data fields
    ("pr30.json", ("train.scan_steps", "train.rounds_per_scan")),
])
def test_from_dict_loads_an_older_commits_config_json(name, removed):
    d = json.loads((OLD_CONFIGS / name).read_text())
    for key in removed:
        section, field = key.split(".")
        assert field in d[section]
    cfg = ExperimentConfig.from_dict(d)
    assert cfg.fed.num_clients == d["fed"]["num_clients"]
    assert cfg.model.news_dim == d["model"]["news_dim"]


@pytest.mark.parametrize("how", ["apply_overrides", "from_dict", "override_removed"])
def test_unknown_key_still_refused(how):
    """A key that never was a field fails on both paths; a removed one is
    tolerated in a file but not in an operator's override."""
    cfg = ExperimentConfig()
    with pytest.raises(KeyError, match="unknown config key"):
        if how == "apply_overrides":
            cfg.apply_overrides(["train.never_a_field=1"])
        elif how == "from_dict":
            ExperimentConfig.from_dict({"train": {"never_a_field": 1}})
        else:
            cfg.apply_overrides(["train.scan_steps=2"])
