"""SHA-256 2^20-row driver: synthesize once (pickled checkpoint), then
prove at the Era commit rate with live-HBM logging between stages.

Usage: BENCH_REPS=N python scripts/sha2_20_driver.py
Checkpoint: /tmp/sha2_20_asm.pkl (delete to re-synthesize).
"""

import os
import pickle
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CKPT = os.environ.get("SHA20_CKPT", "/tmp/sha2_20_asm.pkl")

# persist compiles (the 2^20 graphs take minutes each); importing bench
# applies the package's compile-cache rule with persist-everything
# thresholds as an import side effect
import bench  # noqa: E402,F401


def log_mem(tag):
    import jax

    live = jax.live_arrays()
    total = sum(a.size * a.dtype.itemsize for a in live)
    print(f"[mem] {tag}: {total / 2**30:.2f} GiB across {len(live)} arrays",
          flush=True)


def get_assembly():
    if os.path.exists(CKPT):
        t0 = time.perf_counter()
        with open(CKPT, "rb") as f:
            asm = pickle.load(f)
        print(f"loaded checkpoint in {time.perf_counter()-t0:.1f}s", flush=True)
        return asm
    from bench import build_sha256

    t0 = time.perf_counter()
    cs = build_sha256(131072)
    print(f"synthesis: {time.perf_counter()-t0:.1f}s", flush=True)
    t0 = time.perf_counter()
    asm = cs.into_assembly()
    print(f"freeze: {time.perf_counter()-t0:.1f}s; trace_len={asm.trace_len}",
          flush=True)
    with open(CKPT + ".tmp", "wb") as f:
        pickle.dump(asm, f, protocol=4)
    os.replace(CKPT + ".tmp", CKPT)
    print("checkpoint saved", flush=True)
    return asm


def main():
    reps = int(os.environ.get("BENCH_REPS", "1"))
    asm = get_assembly()
    from boojum_tpu.prover import ProofConfig, generate_setup, prove, verify

    cfg = ProofConfig(
        fri_lde_factor=int(os.environ.get("BENCH_LDE", "2")),
        merkle_tree_cap_size=32,
        num_queries=int(os.environ.get("BENCH_QUERIES", "100")),
        pow_bits=0,
        fri_final_degree=int(os.environ.get("BENCH_FINAL", "16")),
    )
    log_mem("before setup")
    t0 = time.perf_counter()
    setup = generate_setup(asm, cfg)
    print(f"setup: {time.perf_counter()-t0:.1f}s "
          f"(Q={setup.vk.quotient_degree}, L={setup.vk.fri_lde_factor})",
          flush=True)
    log_mem("after setup")
    t0 = time.perf_counter()
    proof = prove(asm, setup, cfg)
    print(f"prove (cold): {time.perf_counter()-t0:.1f}s", flush=True)
    log_mem("after prove")
    t0 = time.perf_counter()
    ok = verify(setup.vk, proof, asm.gates)
    print(f"verify: {ok} in {time.perf_counter()-t0:.1f}s", flush=True)
    assert ok
    for r in range(reps):
        t0 = time.perf_counter()
        proof = prove(asm, setup, cfg)
        print(f"prove (warm {r}): {time.perf_counter()-t0:.2f}s", flush=True)


if __name__ == "__main__":
    main()
