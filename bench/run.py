"""Runs one benchmark cell once and prints its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``bench/cells/<cell>.json``) names its configuration and its
traffic; the traffic names the mode (``bench/modes/<mode>.py``) that builds
the program, warms it up, measures for ``--seconds`` and checks what it
produced against a plain reference. With ``--trace 0`` the result holds the
cell's end-to-end metrics, with ``--trace 1`` the per-layer metrics that
``bench/metrics/`` can read from the traced window. Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
JAX's compilation cache is kept in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

SETUP_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def per_layer(context: dict, peaks: dict) -> dict:
    from bench import registry

    out = {}
    for m in registry.metrics():
        value = m.read(context, peaks)
        if value is not None:
            out[m.NAME] = {"value": value, "unit": m.UNIT}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    from bench import registry

    cell = registry.cell(args.workload)
    import jax

    try:
        dev = device_info(jax)
    except RuntimeError as e:
        log(f"no accelerator: {e}")
        return 3
    if dev["platform"] != "tpu" or dev["count"] < cell.get("chips", 1):
        log(f"needs {cell.get('chips', 1)} TPU chip(s); JAX found "
            f"{dev['count']} {dev['platform']} device(s)")
        return 3
    peaks_all = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if dev["kind"] not in peaks_all["devices"]:
        log(f"no peaks for device kind {dev['kind']!r} in bench/peaks.json")
        return 3
    peaks = peaks_all["devices"][dev["kind"]]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"{args.workload}: seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}; {dev}; compile cache {enable_compile_cache()}")
    res = registry.mode(cell["job"]["mode"]).run(
        cell, args.seed, args.seconds, bool(args.trace), SETUP_T0, log=log)
    device = dict(dev, memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if args.trace:
        tr = res["context"]["trace"]
        line["metrics"] = per_layer(res["context"], peaks)
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    else:
        line["metrics"] = {k: {"value": v, "unit": u}
                           for k, (v, u) in res["end_to_end"].items()}
    line["device"] = device
    line["compiles_in_window"] = res["compiles_in_window"]
    line["compiled_memory"] = res["compiled_memory"]
    line["checks"] = res["checks"]
    for k, c in res["checks"].items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    log(f"correct: {res['correct']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
