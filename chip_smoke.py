"""Smoke run of paper-lm local-SGD training on TPU chips.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the four-chip sync phase, alone

One chip: paper-lm at its published widths (12 layers, d_model 768, 12
heads, d_ff 3072, vocab 8192; ~119.5M parameters in one f32 bucket of
934,040 x 128), random weights from a fixed seed, trains on synthetic
Markov-chain text through the normal entry points (``LocalBackend`` ->
``build_train`` -> ``fit``): W=4 workers vmapped on the chip, local
batch 8, sequence 128, H=4, grad clip 1.0, 3 global rounds (12 steps).
It runs twice, on the resident Pallas-kernel path (``use_kernel=True``)
and on the tree path (``use_kernel=False``, what the training CLI runs),
and checks that every loss is finite, that the loss falls, that the two
paths agree step by step, and that the compiled resident step holds
compiled kernels (``tpu_custom_call``), not interpreted ones.

Four chips: one worker per chip on a ("data", "model") = (4, 1) mesh,
the same model and data, dense sync and then 1-bit sign sync with wire
packing, each against the same configuration vmapped on one device.
Losses must agree, every sync round must be priced from the compiled
HLO, and the compiled local step may move nothing between chips but
scalar metrics.  The mesh path runs the jnp optimizer and compressor
forms: Pallas launches on sharded buckets are not wired in yet.

Progress goes to stdout; the LAST line is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The script
exits non-zero, without that line, when JAX finds no TPU, when the
repository's ``src/`` is not beside it, or when any check fails.
Timings printed here are smoke timings, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

WORKERS, LOCAL_BATCH, SEQ, H, ROUNDS = 4, 8, 128, 4, 3
# base_batch equals the global batch, so the linearly scaled LR is
# BASE_LR itself and a warm-up (which ramps base -> scaled) would be a
# no-op: none is configured.  Clipping bounds every step.  At LR 0.2 the
# full-width loss fell only 0.001 in 12 steps on a v5e, well inside the
# batch-to-batch noise; LR 1.0 makes the fall several times that noise.
BASE_LR, WARMUP_STEPS, GRAD_CLIP, SEED = 1.0, 0, 1.0, 0
# Per-step loss agreement between two runs of one configuration that
# differ only in how the same arithmetic is laid out (fused Pallas
# kernels vs XLA fusions, a cross-chip all-reduce vs an on-chip mean):
# parameters start identical and differ by rounding, about one f32 ulp
# per update, which 12 steps with bfloat16 activations keep far below
# this bound.  A wrong update — lost momentum, clip or decay, a
# mis-scaled sync — moves the loss by more than 1e-2 within 12 steps.
LOSS_RTOL = 1e-3
# bytes of the largest all-reduce a local step may carry: its scalar
# metrics (loss, xent, lr), never a parameter or gradient
SCALAR_BYTES = 64


def make_run(cfg, *, workers: int, local_batch: int, seq: int, h: int,
             rounds: int, compression: str = "none", wire_pack: bool = False):
    from repro.configs.base import (InputShape, LocalSGDConfig, OptimConfig,
                                    RunConfig)
    shape = InputShape("smoke", seq, workers * local_batch, "train")
    return RunConfig(
        model=cfg.replace(max_seq_len=seq), shape=shape,
        local_sgd=LocalSGDConfig(local_steps=h, sync_compression=compression,
                                 wire_pack=wire_pack),
        optim=OptimConfig(base_lr=BASE_LR, base_batch=shape.global_batch,
                          lr_warmup_steps=WARMUP_STEPS, grad_clip=GRAD_CLIP),
        steps=h * rounds)


def make_data(vocab: int, seq: int, num_seqs: int = 1024):
    from repro.data.synthetic import lm_examples, markov_lm
    return lm_examples(markov_lm(vocab=vocab, num_seqs=num_seqs, seq_len=seq,
                                 seed=SEED))


def train(run, data, *, use_kernel: bool, mesh=None, workers: int,
          local_batch: int) -> dict:
    """One ``fit`` of ``run``: per-step losses, AOT compile seconds of
    local_step and sync, fenced wall seconds per local step, the ledger's
    cost sources and the compiled local_step/sync texts."""
    import jax
    import jax.numpy as jnp

    from repro import telemetry as tele
    from repro.backend.local import LocalBackend
    from repro.data.partition import ShardedBatches
    from repro.launch.train import fit
    from repro.models import base as mbase

    backend = LocalBackend(workers, mesh=mesh, use_kernel=use_kernel)
    bundle = backend.build(run)
    params = mbase.abstract(bundle.specs, jnp.dtype(run.model.param_dtype))
    state = jax.eval_shape(bundle.init, jax.random.PRNGKey(0), params)
    batch = {k: jax.ShapeDtypeStruct((workers, local_batch, run.shape.seq_len),
                                     jnp.int32) for k in ("tokens", "labels")}
    if bundle.state_shardings is not None:
        state = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            state, bundle.state_shardings)
        batch = jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            batch, bundle.batch_shardings)
    t0 = time.perf_counter()
    step_text = bundle.step_lower(state, batch).compile().as_text()
    t1 = time.perf_counter()
    sync_text = bundle.sync_lower(state, plan=bundle.sync_plan,
                                  scope="global").compile().as_text()
    t2 = time.perf_counter()

    tracer = tele.Tracer(fence=True)
    it = ShardedBatches(data, workers, local_batch, seed=SEED)
    _, hist, summary = fit(run, it, bundle=bundle, backend=backend,
                           num_steps=run.steps, seed=SEED, tracer=tracer,
                           log=lambda *_: None)
    return {"losses": [h["loss"] for h in hist],
            "compile_s": {"local_step": t1 - t0, "sync": t2 - t1},
            "step_s": [sp.dur_s for sp in tracer.spans
                       if sp.name == "local_steps"],
            "cost_sources": summary["ledger"]["cost_sources"],
            "step_text": step_text, "sync_text": sync_text}


def losses_agree(a, b, rtol: float = LOSS_RTOL) -> float:
    """Largest relative per-step difference; raises past ``rtol``."""
    if len(a) != len(b):
        raise AssertionError(f"{len(a)} vs {len(b)} steps")
    worst = max(abs(x - y) / max(abs(y), 1e-12) for x, y in zip(a, b))
    if not worst <= rtol:
        raise AssertionError(f"losses differ by {worst:.3g} > {rtol:g}:\n"
                             f"{a}\n{b}")
    return worst


def check_losses(losses) -> None:
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: step 1 {losses[0]}, "
                             f"step {len(losses)} {losses[-1]}")


def _timing(label: str, res: dict, log) -> None:
    log(f"{label}: compile_s local_step={res['compile_s']['local_step']:.2f} "
        f"sync={res['compile_s']['sync']:.2f}; smoke timing, not a "
        f"benchmark metric: wall s per local step "
        f"{[round(s, 4) for s in res['step_s']]}")


def one_chip_phase(cfg, *, workers: int = WORKERS,
                   local_batch: int = LOCAL_BATCH, seq: int = SEQ,
                   h: int = H, rounds: int = ROUNDS, log=print) -> dict:
    """Resident kernel path vs tree path through ``fit``; raises on a
    failed loss check.  Returns both runs' results."""
    run = make_run(cfg, workers=workers, local_batch=local_batch, seq=seq,
                   h=h, rounds=rounds)
    data = make_data(cfg.vocab_size, seq)
    out = {}
    for name, use_kernel in (("resident", True), ("tree", False)):
        res = train(run, data, use_kernel=use_kernel, workers=workers,
                    local_batch=local_batch)
        log(f"{name} (use_kernel={use_kernel}) losses: {res['losses']}")
        _timing(name, res, log)
        check_losses(res["losses"])
        out[name] = res
    worst = losses_agree(out["resident"]["losses"], out["tree"]["losses"])
    log(f"resident vs tree: max relative loss difference {worst:.3g} "
        f"(bound {LOSS_RTOL:g})")
    out["max_rel_diff"] = worst
    return out


def collective_census(text: str) -> dict:
    from repro.roofline.hlo import parse_collectives
    census: dict = {}
    for op in parse_collectives(text).ops:
        c = census.setdefault(op.op, {"count": 0, "max_bytes": 0})
        c["count"] += 1
        c["max_bytes"] = max(c["max_bytes"], op.result_bytes)
    return census


def four_chip_phase(cfg, devices, *, local_batch: int = LOCAL_BATCH,
                    seq: int = SEQ, h: int = H, rounds: int = ROUNDS,
                    log=print) -> dict:
    """One worker per device on a (len(devices), 1) mesh, dense and
    1-bit wire-packed sync, each against the same run vmapped on one
    device; raises on a failed check."""
    import numpy as np
    from jax.sharding import Mesh

    workers = len(devices)
    mesh = Mesh(np.array(devices).reshape(workers, 1), ("data", "model"))
    data = make_data(cfg.vocab_size, seq)
    log("mesh path: jnp optimizer and compressor forms (Pallas launches "
        "on sharded buckets are not wired in)")
    out = {}
    for name, comp, wire in (("dense", "none", False), ("sign", "sign", True)):
        run = make_run(cfg, workers=workers, local_batch=local_batch,
                       seq=seq, h=h, rounds=rounds, compression=comp,
                       wire_pack=wire)
        res = train(run, data, use_kernel=True, mesh=mesh, workers=workers,
                    local_batch=local_batch)
        ref = train(run, data, use_kernel=True, workers=workers,
                    local_batch=local_batch)
        log(f"{name} mesh losses: {res['losses']}")
        log(f"{name} one-device losses: {ref['losses']}")
        _timing(f"{name} mesh", res, log)
        _timing(f"{name} one-device", ref, log)
        check_losses(res["losses"])
        worst = losses_agree(res["losses"], ref["losses"])
        log(f"{name}: max relative loss difference {worst:.3g} "
            f"(bound {LOSS_RTOL:g}); ledger cost sources "
            f"{res['cost_sources']}")
        if res["cost_sources"] != ["hlo"]:
            raise AssertionError(f"{name}: sync rounds not priced from the "
                                 f"compiled HLO: {res['cost_sources']}")
        step_c = collective_census(res["step_text"])
        sync_c = collective_census(res["sync_text"])
        log(f"{name} collectives: local_step {step_c}; sync {sync_c}")
        moved = {op: c for op, c in step_c.items()
                 if op != "all-reduce" or c["max_bytes"] > SCALAR_BYTES}
        if moved:
            raise AssertionError(f"{name}: local_step moves more than "
                                 f"scalar metrics between chips: {moved}")
        out[name] = {"mesh": res, "ref": ref, "max_rel_diff": worst,
                     "step_collectives": step_c, "sync_collectives": sync_c}
    return out


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: resident vs tree path on one chip; 4: only the "
                         "four-chip sync phase")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repository source at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.utils import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro import configs
    print(f"compile cache: {cache_dir}")
    dev = devices[0]
    print(f"device_kind: {dev.device_kind}; devices: {len(devices)}")
    cfg = configs.get("paper-lm")
    try:
        if args.chips == 1:
            res = one_chip_phase(cfg)
            if "tpu_custom_call" not in res["resident"]["step_text"]:
                raise AssertionError("the compiled resident local_step holds "
                                     "no compiled Pallas kernel")
            print("resident local_step: compiled Pallas kernels present")
        else:
            four_chip_phase(cfg, devices[:4])
    except AssertionError as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(f"peak_bytes_in_use: "
          f"{[_peak_bytes(d) for d in devices[:args.chips]]}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
