"""The harness's entry point without a chip, and the cell files it finds
by name."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import run


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload",
         "boutique-59k-steady", "--seed", "3", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=run.ROOT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run.load_cell("no-such-cell")


def test_every_cell_finds_its_files():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for w in b["workloads"]:
        spec = run.load_cell(w["name"])
        assert spec["config"]["builder"] == "sizing"
        assert spec["traffic"]["generator"] == "mix_schedule"
        assert spec["end_to_end"] and spec["per_layer"]
        for m in spec["per_layer"]:
            assert os.path.exists(os.path.join(run.BENCH, "metrics",
                                               m["name"] + ".py"))


def test_seeds_above_32_bits_give_valid_sub_seeds():
    s = run.seeds_of(2 ** 33 + 7)
    assert 0 <= s["controller"] < 2 ** 31
    assert s == run.seeds_of(2 ** 33 + 7) != run.seeds_of(2 ** 33 + 8)
