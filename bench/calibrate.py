"""Readings that the limits of a training cell's output check are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--sweep-depths 2,7,14,28 --sweep-seeds 3]

For each seed, in one process: the program's first steps as a run makes
them (``modes/train.py``) and the plain reference; on the first
``--control-seeds`` seeds also

* ``control``: the program with its parameters and their updates kept one
  precision step below the configuration's ``param_dtype`` (the program's
  own path, switched on through its configuration);
* ``control_compute``: the reference put in the program's place with the
  forward values one step below the compute dtype (``reference.py``'s
  ``lowp``);
* ``half_batch``: the reference put in the program's place with half of
  each batch left out (the mean over the rest);
* ``split_sums``: the reference with every GeMM summed in another order,
  which is no fault: it shows how far rounding alone moves each number.

A step that returns its state unchanged reads 1 on ``grad1`` and ``update``
by construction; it is listed without a run. ``--sweep-depths`` compares
the first step's loss (a forward pass) of the program, the reference and
the reference summed in another order at each depth. Prints one JSON line
per reading and a summary (lower reading: the largest over the program's
seeds; upper: the smallest over each other kind) and writes them to
``--out``. It is a tool for setting limits, not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
LOWER = {"float64": "float32", "float32": "bfloat16"}


def lowered(model):
    """The configuration with ``param_dtype`` one precision step down."""
    dt = model["run_dtypes"]
    return dict(model, run_dtypes=dict(
        dt, param_dtype=LOWER[dt["param_dtype"]]))


def _program(b, seed):
    from bench.modes import train

    run = train.Run(b, seed)
    rec = run.check_steps()
    run.free()
    return rec


def calibrate(cell, seeds, control_seeds, log=print):
    import jax
    from bench import compare, reference
    from bench.modes import train

    model, job = cell["model"], cell["job"]
    b = train.build(model, job)
    b_low = train.build(lowered(model), job) if control_seeds else None
    rows, records = [], []
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        prog = _program(b, seed)
        batches = [train.stream(model, job, seed).batch(k)
                   for k in range(job["check_steps"])]
        ref = reference.train_record(model, job, seed, batches)
        kinds = {"program": prog}
        if i < control_seeds:
            kinds["control"] = _program(b_low, seed)
            kinds["control_compute"] = reference.train_record(
                model, job, seed, batches, lowp=True)
            kinds["half_batch"] = reference.train_record(
                model, job, seed, batches, half_batch=True)
            kinds["split_sums"] = reference.train_record(
                model, job, seed, batches, split_sums=True)
            kinds["unchanged"] = {
                "losses": [prog["losses"][0]] * len(prog["losses"]),
                "grad_norms": prog["grad_norms"],
                "grad1": jax.tree.map(lambda x: 0 * x, ref["grad1"]),
                "change": jax.tree.map(lambda x: 0 * x, ref["change"])}
        kinds["reference"] = ref
        for kind, rec in kinds.items():
            records.append({"seed": seed, "kind": kind, **_plain(rec)})
            if kind == "reference":
                continue
            row = {"seed": seed, "kind": kind,
                   "readings": compare.readings(rec, ref),
                   "worst": compare.worst_leaves(rec, ref),
                   "losses": rec["losses"], "ref_losses": ref["losses"]}
            rows.append(row)
            log(json.dumps(row))
        log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")
    return rows, summary(rows), records


def depth_sweep(cell, depths, seeds, log=print):
    """The first step's loss of the program, the reference and the
    reference summed in another order, at each depth (the configuration's
    other sizes as they are)."""
    import jax
    from bench import reference
    from bench.modes import train
    from repro.core.policy import PrecisionPolicy
    from repro.train.trainer import make_loss_fn

    job = cell["job"]
    rows = []
    for depth in depths:
        model = reference._Hashable(dict(cell["model"],
                                         num_hidden_layers=depth))
        pm = train.build(model, job).program_model
        policy = PrecisionPolicy.parse(job["recipe"])
        loss = make_loss_fn(pm, policy)
        prog = jax.jit(lambda p, t, k: loss(
            p, {"tokens": t}, k, pm.prepare_qweights(p, policy))[0])
        for seed in seeds:
            key = reference.seed_key(seed)
            params = reference.WEIGHTS(model, key)
            tokens = jax.numpy.asarray(train.stream(model, job, seed).batch(0))
            k0 = jax.random.fold_in(key, 0)
            row = {"depth": depth, "seed": seed,
                   "program": float(prog(params, tokens, k0))}
            with jax.default_matmul_precision("highest"):
                for name, split in (("reference", False),
                                    ("split_sums", True)):
                    row[name] = float(reference.LOSS(
                        params, tokens, k0, model=model,
                        recipe=job["recipe"], split_sums=split))
            r = row["reference"]
            row["gap_program"] = abs(row["program"] - r) / r
            row["gap_split_sums"] = abs(row["split_sums"] - r) / r
            rows.append(row)
            log(json.dumps(row))
            del params
    return rows


def _plain(rec):
    """A record with numpy arrays as lists, for JSON."""
    import numpy as np
    return {k: ({n: np.asarray(v).tolist() for n, v in rec[k].items()}
                if isinstance(rec[k], dict) else rec[k]) for k in rec}


def summary(rows):
    out = {}
    for kind in sorted({r["kind"] for r in rows}):
        vals = [r["readings"] for r in rows if r["kind"] == kind]
        agg = max if kind == "program" else min
        out[kind] = {k: agg(v[k] for v in vals) for k in vals[0]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000003)
    ap.add_argument("--sweep-depths", default="")
    ap.add_argument("--sweep-seeds", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    import os
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    from bench import registry
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = registry.cell(args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    log = lambda s: print(s, flush=True)  # noqa: E731
    out = {}
    if args.sweep_depths:
        out["sweep"] = depth_sweep(
            cell, [int(d) for d in args.sweep_depths.split(",")],
            [args.first_seed + 7919 * i for i in range(args.sweep_seeds)],
            log=log)
    if args.seeds:
        rows, summ, records = calibrate(cell, seeds, args.control_seeds,
                                        log=log)
        print(json.dumps({"summary": summ}), flush=True)
        out.update(rows=rows, summary=summ, records=records)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
