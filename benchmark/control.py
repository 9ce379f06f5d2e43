"""The control: the cell run with the guarantee its configuration states
broken, which the check must call not correct.

    python3 -m benchmark.control --workload att-backlog --seconds 10 --seeds 1 2 3

The configuration states that a batch verification answers true only if
every signature set in it is valid. The control swaps the node's BLS
backend for one that accepts every signature (the program's own
`fake_crypto` backend) once the node is up, and runs the rest of the cell
unchanged: the invalid messages are then accepted. It prints, for each
seed, the numbers compared and their limits. The benchmark's own runs
never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from . import run as bench_run


def accept_every_signature(node):
    """Swap in the fake_crypto backend until the node stops."""
    from lighthouse_tpu.crypto import bls as node_bls

    node.wrap(node_bls, "_backend", lambda _: node_bls._BACKENDS["fake_crypto"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = bench_run.load_json(os.path.join(bench_run.ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = bench_run.load_json(os.path.join(bench_run.ROOT, config["file"]))
    mix = bench_run.load_json(os.path.join(bench_run.HERE, "traffic", f"{cell['traffic']}.json"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(bench_run.CACHE, "jax")
    device = bench_run.device_info()
    if device["platform"] != "tpu":
        print(f"needs a TPU; JAX reports {device}", file=sys.stderr)
        return 2
    for seed in args.seeds:
        run = bench_run.run_cell(
            cell, cfg, mix, seed, args.seconds, False, time.perf_counter(),
            fault=accept_every_signature,
        )
        print(json.dumps({
            "seed": seed,
            "correct": bench_run.correct(run),
            "checks": run.checks,
            "compared": run.compared,
            "in_window": run.in_window,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
