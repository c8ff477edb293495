"""The on-chip benchmark's harness: files, discovery, refusals, traffic."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import check, registry, traffic  # noqa: E402
from benchmarks.chip import run as harness  # noqa: E402

BENCH = registry.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRIC_KEYS = {"name", "unit", "better", "source", "workloads"}


def _env_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert all(p.startswith(("benchmarks/chip", "tests/benchmark_chip"))
               for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = len(BENCH["workloads"])
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, cells // 2)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert set(m) <= METRIC_KEYS | {"bound"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= METRIC_KEYS | {"layer", "moves"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_every_cell_resolves_from_its_files(cell, trace):
    got = harness.load_cell(cell, trace)
    assert got["workload"]["name"] == cell
    assert set(got["limits"]) == set(check.NAMES)
    section = "per_layer" if trace else "end_to_end"
    assert {m["name"] for m in got["metrics"]} == {
        m["name"] for m in BENCH[section]}
    if trace:
        assert set(got["readers"]) == {m["name"] for m in got["metrics"]}
        assert all(callable(r) for r in got["readers"].values())


def test_an_unknown_workload_is_refused(capsys):
    rc = harness.main(["--workload", "no-such-cell", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no-such-cell" in out.err


def test_a_new_cell_config_and_metric_are_files_only(tmp_path):
    """Add a configuration, a traffic mix, a cell and a per-layer metric
    as files and entries: the harness finds them with no code edit."""
    here = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "metrics", "cells"):
        shutil.copytree(registry.HERE / d, here / d)
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((registry.HERE / "configs" / "smollm2-135m.json")
                     .read_text())
    cfg.update(name="smollm2-135m-d6", num_hidden_layers=6)
    (here / "configs" / "smollm2-135m-d6.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "smollm2-135m-d6", "source": "x",
                             "file": "benchmarks/chip/configs/"
                                     "smollm2-135m-d6.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    mix = json.loads((registry.HERE / "traffic" / "w4.s128.h4.json")
                     .read_text())
    mix["local_steps"] = 8
    (here / "traffic" / "w4.s128.h8.json").write_text(json.dumps(mix))
    bench["workloads"].append({"name": "smollm2-135m-d6.w4.s128.h8",
                               "config": "smollm2-135m-d6",
                               "traffic": "w4.s128.h8", "chips": 1,
                               "why": "x"})
    shutil.copy(here / "cells" / "smollm2-135m.w4.s128.h4.json",
                here / "cells" / "smollm2-135m-d6.w4.s128.h8.json")
    (here / "metrics" / "rounds_in_window.py").write_text(
        "def read(w):\n    return float(w.rounds)\n")
    bench["per_layer"].append({"name": "rounds_in_window", "unit": "1",
                               "better": "higher",
                               "source": "program_span",
                               "layer": "driver loop",
                               "moves": "tokens_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    got = registry.load_benchmark(tmp_path)
    wl = registry.workload(got, "smollm2-135m-d6.w4.s128.h8")
    assert registry.load_config(got, wl["config"],
                                tmp_path)["num_hidden_layers"] == 6
    assert registry.load_traffic(wl["traffic"], here)["local_steps"] == 8
    assert set(check.load_limits(wl["name"], here)) == set(check.NAMES)
    assert "rounds_in_window" in {m["name"] for m in got["per_layer"]}
    read = registry.load_reader("rounds_in_window", here)

    class W:
        rounds = 7
    assert read(W()) == 7.0
    with pytest.raises(registry.UnknownWorkload):
        registry.workload(got, "smollm2-135m-d6.w4.s128.h16")


def test_the_measurement_path_exits_non_zero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env_cpu(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_the_benchmark_alone_exits_non_zero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths
    holds no system to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env_cpu(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "src" in p.stderr


@pytest.mark.parametrize("seed", [0, 2**31 + 17])
def test_markov_tokens_are_the_program_generators_draws(seed):
    from repro.data.synthetic import markov_lm
    want = markov_lm(vocab=97, num_seqs=12, seq_len=40, seed=seed)
    got = traffic.markov_tokens(vocab=97, num_seqs=12, seq_len=40,
                                seed=seed)
    np.testing.assert_array_equal(got, want)


def test_worker_rows_follow_the_programs_data_feed():
    from repro.data.partition import ShardedBatches
    n, W, B, steps, seed = 50, 4, 3, 11, 2**31 + 5
    data = {"i": np.arange(n)}
    feed = ShardedBatches(data, W, B, seed=seed)
    want = np.stack([next(feed)["i"] for _ in range(steps)])
    np.testing.assert_array_equal(
        traffic.worker_rows(n, W, B, steps, seed=seed), want)
