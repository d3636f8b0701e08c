"""Cells cut to a size a test run holds on the CPU: the cells of
``BENCHMARK.json``, and the held fleet cells, whose configuration and
churn are kept under ``data/`` (no published source backs their churn
yet, so they are not cells of the benchmark)."""

from __future__ import annotations

import json
import os
import time

import run

#: fleet tenants and trace rounds of the cut fleet cells
TENANTS = 32
FLEET_ROUNDS = 60
SIZING_ROUNDS = 40

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: held cell -> (configuration, traffic) under ``data/``
HELD = {"fleet-1k-steady": ("ec2-fleet-1k", "churn-steady"),
        "fleet-1k-burst": ("ec2-fleet-1k", "churn-burst")}


def load(cell: str) -> dict:
    if cell not in HELD:
        return run.load_cell(cell)
    cfg, traffic = (json.load(open(os.path.join(DATA, n + ".json")))
                    for n in HELD[cell])
    return {"cell": {"name": cell, "chips": 1}, "config": cfg,
            "traffic": traffic, "end_to_end": [], "per_layer": []}


def small_spec(cell: str) -> dict:
    spec = load(cell)
    if "n_tenants" in spec["config"]:
        spec["config"]["n_tenants"] = TENANTS
        spec["traffic"]["max_rounds"] = FLEET_ROUNDS
    else:
        spec["traffic"]["max_rounds"] = SIZING_ROUNDS
    return spec


def run_small(cell: str, seed: int, seconds: float = 60.0) -> dict:
    """One run of the cut cell through the harness, past its look for a
    chip; the window ends when the cut traffic runs out."""
    return run.run_cell(small_spec(cell), seed, seconds, False,
                        time.perf_counter())
