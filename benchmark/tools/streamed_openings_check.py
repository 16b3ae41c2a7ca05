#!/usr/bin/env python3
"""The opened witness rows of a streamed proof against the plain reference
(`streamed_commit_reference.py`), by hand, in two steps:

    # on the chip: one prove of the cell through the harness's own steps,
    # its proof bytes kept
    python3 benchmark/tools/streamed_openings_check.py prove \
        --workload keccak256-era-512k.closed-12k --seed N --out chiprun_out/x
    # anywhere (numpy and Python ints; no device): the comparison
    JAX_PLATFORMS=cpu python3 benchmark/tools/streamed_openings_check.py check \
        --workload keccak256-era-512k.closed-12k --seed N \
        --proof chiprun_out/x/proof.N.json

The comparison takes nothing from the prover but the proof: the witness
oracle's columns are the assembly's own VALUES over the trace (synthesized
again from the seed), from which the reference makes the monomials (its own
inverse transform), the values on the rate-L domain (its own forward
transform) and the leaf digests (its own sponge). For every query of the
proof it then demands that the opened witness row IS a leaf row of the
reference (found by its first column; all 155 values compared), and that the
reference's digest of that row, walked up the proof's path from that leaf's
index, lands on the proof's witness cap. Exact arithmetic: equality, no
tolerance. A whole reference tree at 2^20 leaves is 21 M permutations of
0.15 ms, an hour of Python: the paths above the leaves are checked as
`verify()` checks them, against the cap the transcript absorbed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import streamed_commit_reference as REF  # noqa: E402

COLUMN_BLOCK = 16  # columns a transform: 16 x 2^20 x 8 B = 128 MiB an array


def witness_oracle_values(asm) -> np.ndarray:
    """The witness oracle's columns over the trace, in the oracle's order:
    copy columns, specialized lookup columns, witness columns, the
    multiplicity column (reference prover.rs; `_prove_impl`'s upload)."""
    parts = [np.asarray(asm.copy_cols_values, np.uint64)]
    if asm.num_lookup_cols:
        parts.append(np.asarray(asm.lookup_cols_values, np.uint64))
    if asm.wit_placement.shape[0]:
        parts.append(np.asarray(asm.wit_cols_values, np.uint64))
    if asm.lookups_enabled:
        parts.append(np.asarray(asm.multiplicities, np.uint64)[None, :])
    return np.concatenate(parts, axis=0)


def _place_rows(lde: np.ndarray, opened: np.ndarray) -> np.ndarray:
    """The leaf index of every opened row, from the first block of columns:
    the leaves whose first value is the row's, and of those the one whose
    block is the row's (the first of them if none is: the comparison then
    counts what differs)."""
    N = lde.shape[1]
    order = np.argsort(lde[0], kind="stable")
    ranked = lde[0][order]
    index = []
    for row in opened:
        lo = int(np.searchsorted(ranked, row[0], side="left"))
        hi = int(np.searchsorted(ranked, row[0], side="right"))
        found = [c for c in order[lo:hi] if np.array_equal(lde[:, c], row)]
        index.append(found[0] if found else order[min(lo, N - 1)])
    return np.array(index, np.int64)


def check_witness_openings(asm, cfg, proof: dict) -> dict:
    """Counts of what differs: opened values that are not the reference's,
    and paths that do not carry the reference's digest to the proof's cap."""
    values = witness_oracle_values(asm)
    L, cap = int(cfg.fri_lde_factor), int(cfg.merkle_tree_cap_size)
    B, n = values.shape
    N = n * L
    queries = [q["witness"] for q in proof["queries"]]
    opened = np.array(
        [[int(v) for v in q["leaf_values"]] for q in queries], np.uint64
    )
    assert opened.shape == (len(queries), B), opened.shape
    rows = np.zeros((len(queries), B), np.uint64)
    index = None
    for i in range(0, B, COLUMN_BLOCK):
        lde = REF.lde_values(REF.intt(values[i : i + COLUMN_BLOCK]), L)
        if index is None:
            index = _place_rows(lde, opened[:, : lde.shape[0]])
        rows[:, i : i + COLUMN_BLOCK] = lde[:, index].T
    differing = int(np.count_nonzero(rows != opened))
    cap_nodes = [tuple(int(v) for v in node) for node in proof["witness_cap"]]
    off = 0
    for q, row, leaf in zip(queries, rows, index):
        at, node = REF.cap_from_path(
            REF.leaf_digest(row), int(leaf), q["path"], N, cap
        )
        off += node != cap_nodes[at]
    return {"queries": len(queries), "columns": B,
            "values_differing": differing, "paths_off_the_cap": off}


def prove(opts) -> int:
    """One run of the cell through `benchmark/run.py`'s own `main`, with a
    system that keeps the first proof's bytes."""
    from benchmark import run
    from benchmark.system import BoojumSystem

    os.makedirs(opts.out, exist_ok=True)
    path = os.path.join(opts.out, f"proof.{opts.seed}.json")

    class Keeping(BoojumSystem):
        def proof_bytes(self, proof) -> bytes:
            blob = super().proof_bytes(proof)
            if not os.path.exists(path):
                with open(path, "wb") as f:
                    f.write(blob)
            return blob

    if os.path.exists(path):
        os.remove(path)
    return run.main(
        ["--workload", opts.workload, "--seed", str(opts.seed),
         "--seconds", "1", "--trace", "0"],
        system=Keeping(),
    )


def check(opts) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import boojum_tpu  # noqa: F401 — x64 before the builder's arrays
    from benchmark import run
    from benchmark.system import BoojumSystem

    cell, system = run.load_cell(opts.workload, ROOT), BoojumSystem()
    system.synthesize(cell, opts.seed)
    with open(opts.proof) as f:
        proof = json.load(f)
    found = check_witness_openings(system.asm, system.cfg, proof)
    found["trace_len"] = int(system.asm.trace_len)
    found["equal"] = not (found["values_differing"] or found["paths_off_the_cap"])
    print(json.dumps(found), flush=True)
    return 0 if found["equal"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="step", required=True)
    for name in ("prove", "check"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seed", type=int, required=True)
    sub.choices["prove"].add_argument("--out", required=True)
    sub.choices["check"].add_argument("--proof", required=True)
    opts = ap.parse_args(argv)
    return prove(opts) if opts.step == "prove" else check(opts)


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
