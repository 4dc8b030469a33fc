"""Shared helpers of the chipbench tests: a throw-away copy of ``chipbench/``
with the test-only ``pendulum_tiny`` cell added as new files."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = "pendulum_tiny.learn"
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def tiny_copy(dst: str) -> str:
    """``chipbench/`` copied to ``dst`` with the tiny cell's files added; no
    file of the copy is edited."""
    root = os.path.join(dst, "chipbench")
    shutil.copytree(os.path.join(REPO, "chipbench"), root)
    for kind in ("configs", "workloads"):
        for name in os.listdir(os.path.join(DATA, kind)):
            shutil.copy(os.path.join(DATA, kind, name), os.path.join(root, kind, name))
    return root


def bench_with(cell: str, config: str, traffic: str, per_layer=(), like=None) -> dict:
    """``BENCHMARK.json`` with one more cell, as a later PR would add it:
    the cell joins the workloads and the ``workloads`` list of every metric
    that the cell ``like`` reports."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append(
        {"name": cell, "config": config, "traffic": traffic, "chips": 1, "why": "test"}
    )
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
    bench["per_layer"].extend(per_layer)
    return bench
