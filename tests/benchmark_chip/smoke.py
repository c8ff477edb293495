"""Smoke-width stand-ins of the benchmark's cells for CPU tests: each
configuration at small widths and depth and each traffic mix at a
short sequence and a small batch, with layout, H and optimizer kept."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import registry  # noqa: E402

BENCH = registry.load_benchmark()
WIDTHS = {"num_hidden_layers": 3, "hidden_size": 128,
          "num_attention_heads": 4, "head_dim": 32,
          "intermediate_size": 256, "vocab_size": 512}


def config(name: str, **over) -> dict:
    cfg = dict(registry.load_config(BENCH, name))
    kv = 4 if cfg["num_key_value_heads"] == cfg["num_attention_heads"] else 2
    cfg.update(WIDTHS, num_key_value_heads=kv)
    cfg.update(over)
    return cfg


def mix(name: str, **over) -> dict:
    tr = dict(registry.load_traffic(name))
    tr.update(seq_len=32, local_batch=min(tr["local_batch"], 2), rows=64)
    tr.update(over)
    return tr


def cell(workload: str, **over) -> dict:
    """A cell dict as ``run.load_cell`` makes it, at smoke size, with the
    workload's own limits."""
    from benchmarks.chip import check
    wl = registry.workload(BENCH, workload)
    return {"workload": wl, "config": config(wl["config"]),
            "traffic": mix(wl["traffic"], **over),
            "limits": check.load_limits(workload),
            "metrics": BENCH["end_to_end"],
            "readers": {}}
