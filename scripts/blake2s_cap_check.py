#!/usr/bin/env python3
"""On the chip, once and outside any measured window: a Blake2s cell's
witness cap rebuilt with `hashlib` from the witness oracle's LDE pulled to
the host, at the published widths, against the cap of a proof the normal
`prove()` made.

    python3 scripts/blake2s_cap_check.py [--workload sha256-blake2s-lde8.closed-8k] [--seed N]

The circuit, the witness and the kept setup are the benchmark cell's own
(`benchmark/system.py`; run the cell first, so that its setup is kept).
The LDE is made again by the library's own transforms (the prove frees
its own), pulled as limb planes, joined on the host and hashed row by row
by `boojum_tpu/compat/blake2s_tree.py`: 93 columns x 2^19 leaves is 390 MB
and some seconds. Prints one JSON line and exits 0 if the caps are equal
and the proof verifies. No CPU fallback: the harness's device guard.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="sha256-blake2s-lde8.closed-8k")
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args()

    from benchmark import run as R
    from benchmark.system import BoojumSystem

    cell = R.load_cell(a.workload)
    system = BoojumSystem()
    devices = system.start()
    R.require_devices(devices, cell["chips"])
    trace_len = system.synthesize(cell, a.seed)
    kept = os.path.join(
        system.cache_dir,
        f"bench.{cell['name']}.{system.setup_key(cell, trace_len)}.setup.pkl",
    )
    if os.path.exists(kept):
        system.load_setup(kept)
    else:
        system.generate_setup()
    asm, setup, cfg = system.asm, system.setup, system.cfg
    assert setup.vk.tree_hasher == "blake2s", setup.vk.tree_hasher

    t0 = time.perf_counter()
    proof, counters = system.recorded_prove()
    prove_s = time.perf_counter() - t0
    verified = system.verify(proof)

    import numpy as np

    from boojum_tpu.compat import blake2s_tree as ref
    from boojum_tpu.ntt import limb_ntt as LN
    from boojum_tpu.utils import transfer

    host_cols = [np.asarray(asm.copy_cols_values)]
    if asm.num_lookup_cols:
        host_cols.append(np.asarray(asm.lookup_cols_values))
    if asm.wit_placement.shape[0]:
        host_cols.append(np.asarray(asm.wit_cols_values))
    if asm.lookups_enabled:
        host_cols.append(np.asarray(asm.multiplicities)[None, :])
    values_p = transfer.chunked_upload(host_cols, planes=True)
    lde_p = LN.lde_from_monomial_p(
        LN.monomial_from_values_p(values_p), cfg.fri_lde_factor
    )
    t0 = time.perf_counter()
    lo, hi = np.asarray(lde_p[0]), np.asarray(lde_p[1])
    B = lo.shape[0]
    rows = (
        lo.astype(np.uint64) | (hi.astype(np.uint64) << np.uint64(32))
    ).reshape(B, -1).T
    pull_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    layers = ref.tree_layers(rows, cfg.merkle_tree_cap_size)
    hash_s = time.perf_counter() - t0
    cap_ref = ref.cap_of(layers)
    cap_proof = [tuple(int(w) for w in d) for d in proof.witness_cap]
    line = {
        "workload": a.workload, "seed": a.seed, "trace_len": trace_len,
        "columns": int(B), "leaves": int(rows.shape[0]),
        "lde_bytes": int(rows.nbytes),
        "caps_equal": cap_ref == cap_proof, "verify": bool(verified),
        "merkle.blake2s_compressions": counters.get(
            "merkle.blake2s_compressions"
        ),
        "cap_word_at_or_above_p": any(
            w >= 0xFFFFFFFF00000001 for d in cap_proof for w in d
        ),
        "prove_s_cold": round(prove_s, 3), "pull_s": round(pull_s, 3),
        "hashlib_s": round(hash_s, 3),
        "device": devices[0].device_kind,
    }
    print(json.dumps(line), flush=True)
    return 0 if line["caps_equal"] and verified else 1


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
