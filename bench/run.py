#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator JAX finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration, traffic mix and
metrics are read from ``BENCHMARK.json`` and the files under ``bench/``.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with its limit);
the same numbers end standard error. Exits nonzero, with no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def tpu_device(chips: int) -> dict:
    """The device line, or SystemExit when the chips are not there."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, "
                         f"JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached however quick its compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"bench: no workload {args.workload!r}")
    device = tpu_device(int(cell["chips"]))
    enable_cache()
    import repro  # noqa: F401  (fails here when the program is absent)
    from bench.harness import run_cell

    result, lines = run_cell(BENCH, manifest, args.workload, args.seed,
                             args.seconds, bool(args.trace), T_START,
                             device=device)
    for line in lines:
        print(line, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
