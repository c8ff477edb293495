"""On-chip benchmark of local-SGD training: one cell, one run.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs from the root of a checkout on a machine that holds the chips the
cell asks for, and prints as its last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(with ``--trace 1`` also ``breakdown``), and last the ``checks`` that
decided ``correct``, each number beside its limit (also the last lines
of standard error).

One run:

1. Set-up (``setup_s``): imports, traffic made from the seed, the
   program's bundle built, the first steps of training through ``fit``
   (which compile every program the window runs and give the readings
   the reference is compared with), and one warm round that sizes the
   window.
2. Window: one ``fit`` of as many whole rounds (H local steps and a
   sync) as fill ``--seconds``, ended by ``block_until_ready`` on the
   returned state. With ``--trace 0`` it gives ``tokens_per_s``; with
   ``--trace 1`` a shorter window runs under the profiler, with the
   program's spans annotated, and the per-layer metrics are read from
   its trace by ``metrics/<name>.py``.
3. Check: with the program's state freed, the plain reference
   (``reference.py``) follows the same first steps from the same seed,
   and ``check.py`` compares.

It exits non-zero without printing a result when the workload is
unknown, when the program's source (``src/``) is not in the checkout,
or when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the traced window is kept short: a trace of a few rounds is enough for
# per-op times, and a long one is slow to write and to read back
TRACE_SECONDS = 4.0


class Refused(Exception):
    """The run cannot measure: exit non-zero, print no result."""


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _importable():
    # run as a script, this directory heads sys.path; its module names
    # must not shadow anything, so the package is imported from the root
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_cell(name: str, trace: bool) -> dict:
    """The cell's workload entry, configuration, traffic, limits and the
    readers of the metrics this run reports; raises Refused."""
    from benchmarks.chip import check, registry
    try:
        bench = registry.load_benchmark(ROOT)
        wl = registry.workload(bench, name)
        cell = {"workload": wl,
                "config": registry.load_config(bench, wl["config"], ROOT),
                "traffic": registry.load_traffic(wl["traffic"]),
                "limits": check.load_limits(wl["name"])}
        section = "per_layer" if trace else "end_to_end"
        cell["metrics"] = bench[section]
        cell["readers"] = ({m["name"]: registry.load_reader(m["name"])
                            for m in cell["metrics"]} if trace else {})
    except (KeyError, OSError, ValueError) as e:
        raise Refused(str(e)) from None
    return cell


def chips(count: int):
    """The first ``count`` TPU devices; raises Refused on anything else."""
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program source at {ROOT / 'src'}")
    cache = str(ROOT / ".jax_cache")
    # the cache lives in the checkout at a fixed path, whatever the
    # environment says, so that two checkouts never share one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no accelerator: {e}") from None
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < count:
        raise Refused(f"the cell needs {count} chips; JAX found "
                      f"{len(devices)}")
    return devices[:count]


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def phase(name: str) -> None:
    """Note on standard error how far into the run a phase ends."""
    print(f"phase {name} {time.perf_counter() - T0:.3f} s", file=sys.stderr,
          flush=True)


def measure(cell: dict, args, devices, peak: dict) -> dict:
    """Set-up, window and check of one run; the result's parts."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import check, flops, reference, traffic
    from benchmarks.chip import trace_reduce, training

    cfg, tr = cell["config"], cell["traffic"]
    H = tr["local_steps"]
    tokens = traffic.markov_tokens(vocab=cfg["vocab_size"],
                                   num_seqs=tr["rows"],
                                   seq_len=tr["seq_len"], seed=args.seed,
                                   **tr["markov"])
    phase("traffic")
    trainer = training.Trainer(cfg, tr, devices, tokens)
    phase("build")
    # the reference follows the first three steps, or the first round
    # where a round is longer, so that a sync is among them
    steps = H * math.ceil(3 / H)
    init = reference.make_init(cfg)
    prog = trainer.checked_steps(
        args.seed, steps, lambda: init(jax.random.PRNGKey(args.seed)))
    phase("checked steps")
    round_s, extra_s = trainer.round_seconds(args.seed)
    phase(f"warm round ({round_s:.4f} s, fit's own {extra_s:.4f} s)")

    out = {}
    if not args.trace:
        rounds = training.rounds_for(args.seconds, round_s, extra_s)
        setup_s = time.perf_counter() - T0
        state, hist, wall = trainer.fit(rounds * H, seed=args.seed)
        done = rounds * H * trainer.tokens_per_step
        values = {"tokens_per_s": done / wall, "setup_s": setup_s}
    else:
        rounds = training.rounds_for(min(args.seconds, TRACE_SECONDS),
                                     round_s, extra_s)
        from repro import telemetry as tele
        tracer = tele.Tracer(annotate=True)
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            with jax.profiler.trace(tmp):
                with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                    state, hist, wall = trainer.fit(rounds * H,
                                                    seed=args.seed,
                                                    tracer=tracer)
            red = trace_reduce.reduce(trace_reduce.find_xplane(tmp),
                                      chips=len(devices))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        window = trace_reduce.Window(
            trace=red, spans=tracer.spans, rounds=rounds, local_steps=H,
            tokens=rounds * H * trainer.tokens_per_step, wall_s=wall,
            chips=len(devices), peak=peak,
            flops_per_token=flops.train_flops_per_token(cfg, tr["seq_len"]))
        values = {}
        for name, read in cell["readers"].items():
            v = read(window)
            if v is not None:
                values[name] = v
        out["breakdown"] = red.breakdown()
        out["busy_s"] = red.busy_s
        out["window_s"] = red.window_s
    units = {m["name"]: m["unit"] for m in cell["metrics"]}
    out["metrics"] = {k: {"value": v, "unit": units[k]}
                      for k, v in values.items()}
    phase("window")
    out["attempted"] = len(hist)
    out["failed"] = sum(1 for h in hist if not math.isfinite(h["loss"]))
    out["memory_peak_bytes"] = peak_bytes(devices)
    del state, hist, trainer
    gc.collect()

    ref = reference.Reference(cfg, tr, devices, dtype=jnp.float32).train(
        tokens, seed=args.seed, steps=steps)
    phase("reference")
    ok, checks = check.judge(check.readings(prog, ref), cell["limits"])
    out["correct"] = ok and out["failed"] == 0
    out["checks"] = checks
    return out


def result_line(out: dict, devices) -> dict:
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if "busy_s" in out:
        device.update(busy_s=out["busy_s"], window_s=out["window_s"])
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def main(argv=None) -> int:
    args = parse_args(argv)
    _importable()
    try:
        cell = load_cell(args.workload, bool(args.trace))
        devices = chips(cell["workload"]["chips"])
        from benchmarks.chip import peaks
        peak = peaks.peak(devices[0].device_kind)
    except (Refused, KeyError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    out = measure(cell, args, devices, peak)
    print(json.dumps(result_line(out, devices)), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
