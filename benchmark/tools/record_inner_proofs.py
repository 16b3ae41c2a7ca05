#!/usr/bin/env python3
"""Record the inner proofs the cell `recursive-verifier.closed-aggregate`
verifies in-circuit. By hand, on the chip, once:

    chiprun --chips 1 --timeout 2400 -- python3 benchmark/tools/record_inner_proofs.py \
        --out chiprun_out/recorded [--inner sha256-lde8.closed-8k] [--seeds 0,1]

then copy `inner.<seed>.json.gz` to `benchmark/data/recursive-verifier/` and
their sha256 (printed, and in `<out>/recorded.json`) into
`benchmark/configs/recursive-verifier.json`.

For each seed the accepted inner cell's own circuit (its builder, widths
and request from its configuration and traffic files, the witness from the
seed) is proved through the program's normal `prove()` with the harness's
own steps (`benchmark/system.py`: kernel library on a pool, `generate_setup`
once, the same setup for every seed), `verify()` accepts the proof, and
proof and verification key go through `boojum_tpu.serialization` and
`Proof.to_json` into one gzip file, read back and verified again before
the tool reports it. Like a run, it refuses to work off a TPU.

Remake the files when the inner configuration, the prover's proof bytes or
`boojum_tpu.serialization` change (benchmark/data/README.txt): the outer builder
refuses a proof the host verifier rejects. Not run by the driver.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402


def record(system, cell, seeds, out_dir: str, device_kind: str) -> dict:
    """`system` has been started. Returns {file name: facts}."""
    from boojum_tpu.prover import verify

    # the file format is the outer builder's own (write_recorded)
    outer = system.load_builder({
        "config": {"circuit": {"builder": "recursive_verifier"}},
        "bench_dir": run.BENCH,
    })
    os.makedirs(out_dir, exist_ok=True)
    report = {}
    for at, seed in enumerate(seeds):
        trace_len = system.synthesize(cell, seed)
        if at == 0:
            errors = system.warm_library(max(8, os.cpu_count() or 8))
            if errors:
                raise run.BenchFailure("kernels failed to compile: " + "; ".join(errors))
            system.generate_setup()
        t0 = time.perf_counter()
        proof = system.prove()
        system.drain()
        wall = time.perf_counter() - t0
        if not system.verify(proof):
            raise run.BenchFailure(f"seed {seed}: verify() rejects the proof")
        name = f"inner.{seed}.json.gz"
        path = os.path.join(out_dir, name)
        proof_sha = hashlib.sha256(system.proof_bytes(proof)).hexdigest()
        outer.write_recorded(path, system.setup.vk, proof, {
            "tool": "benchmark/tools/record_inner_proofs.py",
            "inner": cell["name"],
            "seed": seed,
            "trace_len": trace_len,
            "proof_sha256": proof_sha,
            "device_kind": device_kind,
        })
        vk, again, _meta = outer.read_recorded(path)
        if not verify(vk, again, system.asm.gates):
            raise run.BenchFailure(f"{name}: the file read back does not verify")
        with open(path, "rb") as f:
            blob = f.read()
        report[name] = {
            "sha256": hashlib.sha256(blob).hexdigest(),
            "bytes": len(blob),
            "seed": seed,
            "trace_len": trace_len,
            "proof_sha256": proof_sha,
            "prove_s": round(wall, 3),
        }
        run.log(f"{name}: {json.dumps(report[name])}")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--inner", default="sha256-lde8.closed-8k")
    ap.add_argument("--seeds", default="0,1")
    opts = ap.parse_args(argv)
    from benchmark.system import BoojumSystem

    cell = run.load_cell(opts.inner)
    system = BoojumSystem()
    devices = system.start()
    run.require_devices(devices, cell["chips"])
    report = record(
        system, cell, [int(s) for s in opts.seeds.split(",")], opts.out,
        devices[0].device_kind,
    )
    with open(os.path.join(opts.out, "recorded.json"), "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)  # as run.py: no teardown of the TPU runtime
