#!/usr/bin/env python3
"""Compile the prover's whole kernel library for a DESCRIBED TPU v5e, here,
without the chip (the `on-chip-measurement` guide's third rehearsal).

    JAX_PLATFORMS=cpu python scripts/chip_compile_rehearsal.py \
        [--sha-bytes 8192 | --fma-log-n N | --config FILE --traffic MIX] \
        [--skip K] [--only A,B] [--workers W] [--mesh | --force-xla]

`--config benchmark/configs/<name>.json --traffic <mix>` compiles a
benchmark configuration's own library: the circuit comes from the
configuration's builder under `benchmark/circuits/` with the mix's request
(seed 0: the library depends on shapes, not on the witness) and the
`ProofConfig` from the file, exactly as `benchmark/system.py` makes them.

What the chip's compiler would refuse (VMEM limit, unaligned slice, SMEM
table size) it refuses here, at no chip time. Nothing runs: a compile that
passes is not a chip run. The steering is done HERE, not in the program:
`jax.default_backend` is patched to answer "tpu" so the dispatchers pick
the native (non-interpret) limb-resident Pallas set, and every
ShapeDtypeStruct of `enumerate_kernels` gets the described device's
sharding. The persistent cache is off (an entry compiled for an absent
chip cannot be read back). `--mesh` enumerates the shard_map (`_sm`) set
for a 2x2 mesh of the described devices instead; `--force-xla` the u64 XLA
set that `chip_smoke.py`'s parity phase proves against.
"""

import argparse
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["BOOJUM_TPU_NO_COMPILE_CACHE"] = "1"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import boojum_tpu  # noqa: E402,F401 — x64 + the TPU compiler's stack flags
import jax  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def benchmark_cell(config_file: str, traffic: str):
    """(assembly, ProofConfig) of a benchmark configuration under a traffic
    mix, through the harness's own `BoojumSystem.synthesize`."""
    import json

    from benchmark.system import BoojumSystem

    if not traffic:
        raise SystemExit("--config needs --traffic <mix>")
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(config_file)))
    with open(config_file) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "traffic", f"{traffic}.json")) as f:
        mix = json.load(f)
    system = BoojumSystem()
    system.synthesize(
        {"config": config, "traffic": mix, "bench_dir": bench_dir}, seed=0
    )
    return system.asm, system.cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sha-bytes", type=int, default=8192)
    ap.add_argument("--fma-log-n", type=int, default=0)
    ap.add_argument("--config", default="",
                    help="a benchmark configuration file (with --traffic)")
    ap.add_argument("--traffic", default="",
                    help="a traffic mix's name under benchmark/traffic/")
    ap.add_argument("--hbm-gib", type=float, default=15.75,
                    help="device memory of the described chip: the library "
                    "takes its streamed-commit threshold from it")
    ap.add_argument("--skip", type=int, default=0)
    ap.add_argument("--only", default="")
    ap.add_argument("--workers", type=int, default=os.cpu_count() or 4)
    ap.add_argument("--mesh", action="store_true")
    ap.add_argument("--force-xla", action="store_true")
    a = ap.parse_args()

    jax.config.update("jax_enable_compilation_cache", False)
    # the attached backend is the CPU, which reports no memory limit: say
    # what the described chip would report, so that the library enumerates
    # the commit path (materialized or streamed) it would choose there
    from boojum_tpu.prover import streaming

    os.environ.setdefault("BOOJUM_TPU_STREAM_LDE", str(int(max(
        streaming.DEFAULT_STREAM_THRESHOLD,
        streaming.STREAM_SHARE_OF_DEVICE * a.hbm_gib * (1 << 30),
    ))))
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"

    from boojum_tpu import examples
    from boojum_tpu.prover import ProofConfig, enumerate_kernels
    from boojum_tpu.prover.precompile import trim_host_heap

    if a.config:
        asm, cfg = benchmark_cell(a.config, a.traffic)
    else:
        if a.fma_log_n:
            cs = examples.build_fma_bench_circuit(a.fma_log_n)
            lde = 4
        else:
            cs = examples.build_sha256_bench_circuit(a.sha_bytes)
            lde = 8
        cfg = ProofConfig(
            fri_lde_factor=lde, merkle_tree_cap_size=16, num_queries=50,
            pow_bits=0, fri_final_degree=16,
        )
        asm = cs.into_assembly()
    mesh_shape = None
    if a.mesh:
        import numpy as np
        from jax.sharding import Mesh

        mesh_shape = Mesh(
            np.array(topo.devices[:4]).reshape(2, 2), ("col", "row")
        )
    if a.force_xla:
        # for the rest of the run: the dispatchers read it while tracing
        from boojum_tpu.utils.pallas_util import force_xla

        force_xla().__enter__()
    specs = enumerate_kernels(asm, cfg, mesh_shape=mesh_shape)
    print(f"trace_len={asm.trace_len} specs={len(specs)}", flush=True)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def place(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        return x

    def placed_args(s):
        # a shard_map (`_sm`) kernel carries its own mesh of the described
        # devices; every other kernel is told which chip it compiles for
        return s.args if "_sm" in s.name else jax.tree.map(place, s.args)

    todo = [
        (i, s) for i, s in enumerate(specs)
        if i >= a.skip and any(o in s.name for o in a.only.split(","))
    ]
    failed = []

    def refused(i, s, t0, e):
        failed.append(s.name)
        print(
            f"FAIL {i:3d} {s.name} {time.perf_counter() - t0:.1f}s "
            f"{str(e)[:2000]}",
            flush=True,
        )

    # as precompile() does: lower serially on this thread (tracing is
    # Python work), compile on the pool (the compilers release the GIL)
    t0 = time.perf_counter()
    lowered = []
    for i, s in todo:
        t1 = time.perf_counter()
        try:
            lowered.append((i, s, s.fn.lower(*placed_args(s))))
        except Exception as e:  # noqa: BLE001 — the finding this script is for
            refused(i, s, t1, e)
    print(f"lowered {len(lowered)} in {time.perf_counter() - t0:.0f}s", flush=True)

    def one(item):
        i, s, low = item
        t1 = time.perf_counter()
        try:
            txt = low.compile().as_text()
        except Exception as e:  # noqa: BLE001
            return refused(i, s, t1, e)
        finally:
            trim_host_heap()  # as precompile() does after every compile
        kind = "mosaic" if "tpu_custom_call" in txt else "xla"
        print(
            f"ok   {i:3d} {s.name} {time.perf_counter() - t1:.1f}s {kind}",
            flush=True,
        )

    with ThreadPoolExecutor(max_workers=a.workers) as pool:
        list(pool.map(one, lowered))
    print(
        f"done: {len(todo) - len(failed)}/{len(todo)} compiled for "
        f"{topo.devices[0].device_kind} in {time.perf_counter() - t0:.0f}s; "
        f"refused: {failed}",
        flush=True,
    )
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
