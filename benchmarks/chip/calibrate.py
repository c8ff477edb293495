"""Readings that a cell's limits are set from, on the chip.

    python benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... [--controls 3] [--faults 3] [--stacked-layers] \
        --out <file.json>

In one process, with the program's bundle built once: for every seed,
the program's first steps against the float32 reference (the numbers
``check.py`` compares); for the first ``--controls`` seeds the control,
the reference computed in bfloat16 and put in the program's place; for
the first ``--faults`` seeds the faults planted in the reference put in
the program's place (half of each batch left out; the sync left out).
A state left unchanged reads 1 on ``change_gap`` and needs no run.
``--stacked-layers`` runs the program with its layers stacked, whatever
the configuration says (the reference then draws its initial weights as
that layout does). Each program seed also records, per weight, the
largest relative gap of its change from the reference's. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
sys.path.insert(0, str(HERE.parents[1]))

from benchmarks.chip import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--stacked-layers", action="store_true")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    harness._importable()
    try:
        cell = harness.load_cell(args.workload, False)
        devices = harness.chips(cell["workload"]["chips"])
    except harness.Refused as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.chip import check, reference, traffic, training

    cfg, tr = cell["config"], cell["traffic"]
    if args.stacked_layers:
        cfg = dict(cfg, stacked_layers=True)
    H = tr["local_steps"]
    steps = H * math.ceil(3 / H)

    def tokens_of(seed):
        return traffic.markov_tokens(vocab=cfg["vocab_size"],
                                     num_seqs=tr["rows"],
                                     seq_len=tr["seq_len"], seed=seed,
                                     **tr["markov"])

    trainer = training.Trainer(cfg, tr, devices, tokens_of(seeds[0]))
    init = reference.make_init(cfg)
    ref32 = reference.Reference(cfg, tr, devices)
    others = {"control": reference.Reference(cfg, tr, devices,
                                             dtype=jnp.bfloat16),
              "half_batch": reference.Reference(cfg, tr, devices,
                                                fault="half_batch"),
              "no_sync": reference.Reference(cfg, tr, devices,
                                             fault="no_sync")}
    out = {"workload": args.workload,
           "stacked_layers": cfg["stacked_layers"], "steps": steps,
           "program": {}, "change_gap_by_weight": {},
           "control": {}, "half_batch": {}, "no_sync": {}}
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        tokens = tokens_of(seed)
        trainer.data = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
        prog = trainer.checked_steps(
            seed, steps, lambda: init(jax.random.PRNGKey(seed)))
        ref = ref32.train(tokens, seed=seed, steps=steps)
        out["program"][seed] = check.readings(prog, ref)
        out["change_gap_by_weight"][seed] = {
            k: float(np.max(np.abs(prog["change_norms"][k] - r)
                            / np.maximum(r, 1e-30)))
            for k, r in ref["change_norms"].items()}
        for name, r in others.items():
            if i < (args.controls if name == "control" else args.faults):
                out[name][seed] = check.readings(
                    r.train(tokens, seed=seed, steps=steps), ref)
        print(f"seed {seed} ({time.perf_counter() - t0:.1f} s): "
              + json.dumps({k: out[k].get(seed) for k in others | {
                  "program": 0}}), flush=True)
    summary = {}
    for n in check.NAMES:
        summary[n] = {"lower": max(v[n] for v in out["program"].values())}
        for k in others:
            if out[k]:
                summary[n][k] = min(v[n] for v in out[k].values())
    out["summary"] = summary
    print(json.dumps(summary), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
