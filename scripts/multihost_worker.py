"""Worker process for the 2-process jax.distributed multihost tests.

Launched by tests/test_multihost.py with:
  python scripts/multihost_worker.py <mode> <port> <pid> <nprocs> <out.json>

Brings up jax.distributed over localhost (CPU backend, 2 virtual devices per
process), runs the requested DCN mode, and writes its result JSON. Modes:
  proofs  — proof-parallel through the SERVICE worker loop: this process
            submits its distribute_proofs slice of a 3-job queue to a
            local ProvingService (boojum_tpu/service/) and drains it —
            shape-bucketed admission, device-resident caches, per-request
            SLO records; no cross-process collectives. The per-host
            result-line format (proofs dict, ici gauges) is unchanged.
            With BOOJUM_TPU_GATEWAY_SPOOL set (ISSUE 11), the process
            ALSO takes its distribute_proofs slice of the gateway's
            spool directory — one JSON job file per request, written by
            service/gateway.py for bulk-lane admissions — so the
            horizontal tier has a feed path from the network front door.
            Spool specs carry {"job", "tenant", "seed", "priority"};
            each proved job lands in the result line's "spool" dict.
  hybrid  — hybrid_mesh: one proof whose mesh 'col' axis spans both
            processes (GSPMD collectives cross the process boundary)

Every result line carries a `clock_sync` record (ISSUE 15): time.time()
stamped immediately after a global device barrier, so
`prove_report.py --fleet` aligns per-host timelines from the stamps'
pairwise differences instead of assuming NTP-synchronized clocks.
"""

import json
import os
import sys

# must run BEFORE jax import: local CPU with 2 devices per process
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=2"
).strip()
os.environ.pop("PYTHONSTARTUP", None)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the package import applies the one compile-cache rule
# (boojum_tpu/compile_cache.py) — the same directory the test suite fills,
# so the worker starts warm from its compiles
import boojum_tpu  # noqa: E402,F401
import jax  # noqa: E402


def build_circuit(seed: int):
    from boojum_tpu.cs.gates import FmaGate, PublicInputGate
    from boojum_tpu.cs.implementations import ConstraintSystem
    from boojum_tpu.cs.types import CSGeometry

    cs = ConstraintSystem(CSGeometry(8, 0, 6, 4), 1 << 10)
    a = cs.alloc_variable_with_value(1 + seed)
    b = cs.alloc_variable_with_value(2 + seed)
    for _ in range(300):
        a, b = b, FmaGate.fma(cs, a, b, a, 1, 1)
    PublicInputGate.place(cs, b)
    return cs


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("mode", choices=["proofs", "hybrid"])
    ap.add_argument("port", type=int)
    ap.add_argument("pid", type=int)
    ap.add_argument("nprocs", type=int)
    ap.add_argument("out_path")
    ap.add_argument(
        "--mesh-mode", choices=["shard_map", "gspmd"], default=None,
        help="force the hybrid prove's mesh execution mode (sets "
        "BOOJUM_TPU_MESH_MODE before the prove; default: the prover's "
        "own default, shard_map on every topology)",
    )
    args = ap.parse_args()
    mode, port, pid, nprocs, out_path = (
        args.mode, args.port, args.pid, args.nprocs, args.out_path
    )
    if args.mesh_mode:
        os.environ["BOOJUM_TPU_MESH_MODE"] = args.mesh_mode
    from boojum_tpu.parallel.multihost import (
        distribute_proofs,
        hybrid_mesh,
        initialize_multihost,
    )

    active = initialize_multihost(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs,
        process_id=pid,
    )
    assert active, "jax.distributed did not come up multi-process"
    assert jax.process_count() == nprocs

    # flight recorder, per-host: point each process at its own ProveReport
    # artifact (JSONL appends from two processes into one file would
    # interleave); prove() auto-records once the env var is set. With no
    # BOOJUM_TPU_REPORT configured the recorder is armed anyway, next to
    # the result file — MULTICHIP rounds must always record which path
    # (mesh_mode) and which fabric (ici/dcn gauges) actually ran
    report_base = os.environ.get("BOOJUM_TPU_REPORT") or (
        out_path + ".report.jsonl"
    )
    report_path = f"{report_base}.host{pid}"
    os.environ["BOOJUM_TPU_REPORT"] = report_path

    # black-box forensics (ISSUE 15): with BOOJUM_TPU_BLACKBOX /
    # BOOJUM_TPU_STALL_S armed, a host wedged inside a cross-process
    # collective leaves a heartbeat trail + stack dump behind — the
    # per-host artifact `--fleet` aggregates
    try:
        from boojum_tpu.utils import blackbox as _blackbox

        _blackbox.ensure_started(
            label=f"multihost{pid}", report_path=report_path
        )
        _blackbox.set_phase(f"multihost_{mode}")
    except Exception:
        pass

    # hard deadline (ISSUE 16): XLA:CPU's gloo collectives have NO
    # timeout — a cross-process rendezvous whose peer never arrives
    # (observed once on a cold compile cache) blocks forever with zero
    # CPU. Exit 3 with stacks after BOOJUM_TPU_MH_DEADLINE_S (default
    # 1800 s, generous for cold cross-host compiles; 0 disables) so a
    # wedged pair fails the CI leg fast and with forensics instead of
    # silently burning the harness timeout.
    deadline_s = float(os.environ.get("BOOJUM_TPU_MH_DEADLINE_S", "1800"))
    if deadline_s > 0:
        import faulthandler
        import threading

        def _deadline_abort():
            print(
                f"multihost_worker pid={pid}: deadline "
                f"{deadline_s}s exceeded, dumping stacks and exiting",
                file=sys.stderr,
            )
            faulthandler.dump_traceback(file=sys.stderr)
            try:
                from boojum_tpu.utils import blackbox as _bb

                bb = _bb.current_blackbox()
                if bb is not None:
                    bb.dump("deadline", deadline_s=deadline_s)
            except Exception:
                pass
            sys.stderr.flush()
            os._exit(3)

        _t = threading.Timer(deadline_s, _deadline_abort)
        _t.daemon = True
        _t.start()

    # barrier-synchronized wall-clock stamp (ISSUE 15 satellite): every
    # process reads time.time() immediately after passing the SAME
    # global device barrier, so the pairwise differences of these stamps
    # ARE the hosts' wall-clock skews — prove_report.py --fleet aligns
    # per-host timelines from them without assuming NTP
    clock_sync = None
    try:
        import time as _time

        from jax.experimental import multihost_utils as _mhu

        _mhu.sync_global_devices("boojum_tpu_clock_sync")
        clock_sync = {
            "barrier_unix_ts": _time.time(),
            "method": "sync_global_devices",
        }
    except Exception as e:
        print(f"clock sync barrier failed: {e!r}", file=sys.stderr)

    from boojum_tpu.prover import ProofConfig, generate_setup, prove, verify

    cfg = ProofConfig(fri_lde_factor=4, num_queries=8, fri_final_degree=8)

    result = {"pid": pid, "process_count": jax.process_count()}
    if clock_sync is not None:
        result["clock_sync"] = clock_sync
    if mode == "proofs":
        # proof-parallel across hosts: distribute_proofs slices the job
        # queue per process; WITHIN the process the jobs drain through
        # the service worker loop (meshless placement on a multi-process
        # runtime — cross-host parallelism needs no device collectives)
        from boojum_tpu.service import ProvingService, ServiceConfig

        jobs = [0, 1, 2]
        svc = ProvingService(
            ServiceConfig(precompile="off", report_path=report_path)
        )
        assert svc.mesh is None, "multi-process service must stay meshless"

        def submit_job(seed):
            asm = build_circuit(seed).into_assembly()
            setup = generate_setup(asm, cfg)
            return svc.submit(asm, setup, cfg, request_id=f"job-{seed}")

        mine = distribute_proofs(jobs, submit_job)

        # gateway spool feed (ISSUE 11): this host's slice of the front
        # door's bulk-lane spool rides the same service drain
        spool_dir = os.environ.get("BOOJUM_TPU_GATEWAY_SPOOL")
        mine_spool = []
        if spool_dir and os.path.isdir(spool_dir):
            from boojum_tpu.service.gateway import read_spool

            def submit_spool(item):
                _fname, spec = item
                asm = build_circuit(int(spec.get("seed", 0))).into_assembly()
                setup = generate_setup(asm, cfg)
                priority = spec.get("priority", "bulk")
                # trace propagation (ISSUE 17): the spool record carries
                # the trace the GATEWAY minted at POST /prove — submit
                # under it so the fleet's prove lines stitch back to the
                # admission instead of orphaning
                trace = spec.get("trace")
                return svc.submit(
                    asm, setup, cfg,
                    request_id=str(spec.get("job", _fname)),
                    tenant=str(spec.get("tenant", "default")),
                    priority=priority if priority in (
                        "interactive", "batch", "bulk"
                    ) else "bulk",
                    trace=trace if isinstance(trace, dict) else None,
                )

            mine_spool = distribute_proofs(read_spool(spool_dir),
                                           submit_spool)

        summary = svc.run_worker()
        result["service"] = summary
        assert summary["failed"] == 0, summary
        for _i, req in mine:
            assert verify(req.setup.vk, req.result(), req.assembly.gates)
        result["proofs"] = {str(i): req.result().to_json() for i, req in mine}
        # per-job trace ids on the result line (ISSUE 17): fleet-proved
        # jobs must not be orphan traces — the gateway side joins its
        # tickets to the fleet's proves through this map, and the
        # timeline stitcher gets it for free via each prove line's
        # trace_ctx
        result["traces"] = {
            req.id: (req.trace or {}).get("trace_id")
            for _i, req in list(mine) + list(mine_spool)
        }
        if mine_spool:
            for _i, req in mine_spool:
                assert verify(
                    req.setup.vk, req.result(), req.assembly.gates
                )
            result["spool"] = {
                req.id: req.result().to_json() for _i, req in mine_spool
            }
    elif mode == "hybrid":
        mesh = hybrid_mesh(col_axis_per_host=2)
        assert mesh.shape["col"] == nprocs * 2, dict(mesh.shape)
        # record which execution path this prove will take (shard_map =
        # native limb kernels + explicit collectives; gspmd = legacy
        # XLA-partitioned u64) — the parity test and MULTICHIP triage
        # both key on this stamp
        from boojum_tpu.utils.pallas_util import resolve_variant

        result["mesh_mode"] = resolve_variant(mesh).mesh
        asm = build_circuit(0).into_assembly()
        setup = generate_setup(asm, cfg)
        proof = prove(asm, setup, cfg, mesh=mesh)
        result["proof"] = proof.to_json()
    else:
        raise SystemExit(f"unknown mode {mode}")
    result.setdefault("mesh_mode", "none")

    if report_path is not None:
        result["prove_report_path"] = report_path
        # surface the explicit-collective bill (ISSUE 5) and its
        # cross-host split (ISSUE 16) on the per-host line itself: the
        # ici.*/dcn.* gauges/counters of the LAST prove of this host,
        # plus its Fiat-Shamir digest checkpoints, so multi-host runs
        # are triageable (and parity-checkable) without opening every
        # ProveReport artifact
        try:
            with open(report_path) as f:
                lines = [ln for ln in f if ln.strip()]
            last = json.loads(lines[-1])
            metrics = last.get("metrics") or {}
            for fam in ("ici", "dcn"):
                result[fam] = {
                    k: v
                    for src in ("gauges", "counters")
                    for k, v in (metrics.get(src) or {}).items()
                    if k.startswith(f"{fam}.")
                }
            if isinstance(last.get("checkpoints"), list):
                result["checkpoints"] = last["checkpoints"]
        except (OSError, ValueError, IndexError):
            result["ici"] = {}
            result["dcn"] = {}

    with open(out_path, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main()
