"""Shared by the chipbench tests: the repo root on sys.path, and a temporary
benchmark root to which a test adds files (never editing one that is there)."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = dict(hidden_size=256, intermediate_size=512, num_attention_heads=2,
            num_key_value_heads=1, head_dim=128, vocab_size=1024)


def copy_root(tmp: str) -> str:
    """BENCHMARK.json and chipbench's data directories, copied."""
    root = os.path.join(str(tmp), "root")
    os.makedirs(os.path.join(root, "chipbench"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for d in ("configs", "traffic", "jobs", "layer_metrics", "reducers"):
        shutil.copytree(os.path.join(ROOT, "chipbench", d),
                        os.path.join(root, "chipbench", d))
    return root


def read(path):
    with open(path) as f:
        return json.load(f)


def write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def add_cell(root, bench, name, config, traffic, like):
    """A new workload that reports what the cell ``like`` reports."""
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
