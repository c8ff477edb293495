"""Record the small chip trace that test_bench_trace.py reads.

    python tests/benchmark_chip/record_trace.py [--out DIR]   # with a TPU

A two-layer model, 4 workers vmapped, 3 rounds of H=2 through ``fit``
under the profiler, with the benchmark's window annotation and the
program's spans annotated, as ``benchmarks/chip/run.py --trace 1`` does.
Writes ``tiny.xplane.pb`` and, beside it, ``tiny.json`` (the window's
shape and the numbers read from the trace when it was recorded) into
``DIR``, by default ``tests/benchmark_chip/data``.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CFG = {"name": "tiny", "source": "tiny", "num_hidden_layers": 2,
       "hidden_size": 256, "num_attention_heads": 4,
       "num_key_value_heads": 4, "head_dim": 64, "intermediate_size": 512,
       "vocab_size": 1024, "hidden_act": "silu", "rms_norm_eps": 1e-6,
       "rope_theta": 10000.0, "rope_scaling": None,
       "tie_word_embeddings": True, "param_dtype": "float32",
       "stacked_layers": True}
TRAFFIC = {"workers": 4, "placement": "vmap",
           "local_batch": 2, "seq_len": 64, "local_steps": 2, "rows": 64,
           "markov": {"branching": 4, "noise": 0.1},
           "optim": {"base_lr": 1.0, "momentum": 0.9, "nesterov": True,
                     "weight_decay": 0.0, "grad_clip": 1.0}}
ROUNDS, SEED = 3, 7


def main(argv=None) -> int:
    import argparse

    import jax

    from benchmarks.chip import trace_reduce, traffic, training
    from repro import telemetry as tele

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(HERE / "data"))
    dst = Path(ap.parse_args(argv).out)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    tokens = traffic.markov_tokens(vocab=CFG["vocab_size"], num_seqs=64,
                                   seq_len=TRAFFIC["seq_len"], seed=SEED)
    trainer = training.Trainer(CFG, TRAFFIC, devices[:1], tokens)
    trainer.fit(2 * TRAFFIC["local_steps"], seed=SEED)     # compile
    tracer = tele.Tracer(annotate=True)
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        with jax.profiler.trace(tmp):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                _, hist, wall = trainer.fit(ROUNDS * TRAFFIC["local_steps"],
                                            seed=SEED, tracer=tracer)
        src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)[0]
        dst.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst / "tiny.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    red = trace_reduce.reduce(str(dst / "tiny.xplane.pb"), chips=1)
    meta = {"rounds": ROUNDS, "local_steps": TRAFFIC["local_steps"],
            "wall_s": wall, "device_kind": devices[0].device_kind,
            "window_s": red.window_s, "busy_s": red.busy_s,
            "local_steps_program": red.program_s("local_steps"),
            "sync_program": red.program_s("sync"),
            "local_steps_spans": sum(1 for s in tracer.spans
                                     if s.name == "local_steps")}
    with open(dst / "tiny.json", "w") as f:
        json.dump(meta, f, indent=1)
    print(json.dumps(meta))
    return 0


if __name__ == "__main__":
    sys.exit(main())
