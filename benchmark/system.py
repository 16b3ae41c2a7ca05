"""The system under test, as the harness sees it: everything the benchmark
takes from the program goes through this one class (the prover's entry
points, its flight-recorder counters, its span annotations and its
compile-cache rule). Tests put a stand-in with the same methods in its place.
"""

from __future__ import annotations

import hashlib
import importlib.util
import json
import os


CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def native_path_problems(counters: dict) -> list[str]:
    """A copy of chip_smoke.py's check: a prove that verified on the XLA u64
    fallback is a different system, not a slower one."""
    c = lambda k: int(counters.get(k, 0) or 0)  # noqa: E731
    problems = []
    if not (c("quotient.resident_coset_sweeps") == c("quotient.coset_sweeps") > 0):
        problems.append(
            f"quotient.resident_coset_sweeps {c('quotient.resident_coset_sweeps')}"
            f" != quotient.coset_sweeps {c('quotient.coset_sweeps')} (or both zero)"
        )
    if not (c("fri.resident_folds") == c("fri.folds") > 0):
        problems.append(
            f"fri.resident_folds {c('fri.resident_folds')} != fri.folds "
            f"{c('fri.folds')} (or both zero)"
        )
    for k in ("ntt.resident_transforms", "merkle.resident_commits",
              "deep.resident_codewords"):
        if c(k) < 1:
            problems.append(f"{k} is {c(k)}: the resident kernels did not run")
    for k in ("limb.splits", "limb.joins"):
        if c(k) > 0:
            problems.append(f"interior {k} = {c(k)} on a resident prove")
    return problems


class BoojumSystem:
    """boojum_tpu's prover. `start()` imports the package before the backend
    starts (it sets x64, the compile-cache rule and the TPU compiler's stack
    size) and returns jax.devices()."""

    def start(self):
        # The benchmark gives the program its compile cache: a fixed
        # directory inside the checkout, with no size cap. The program's one
        # rule (boojum_tpu/compile_cache.py) takes JAX's variable where it
        # is set. A machine-wide directory would be shared by the two sides
        # of a comparison, and a capped one (the chip machines come with
        # 192 MiB) evicts a cell's own library, which is larger: no run
        # would ever start warm.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
        import boojum_tpu  # noqa: F401
        import jax

        # keep every compiled program, however quick its compile: a warm
        # run then compiles nothing (the package's rule keeps the
        # directory: JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.jax = jax
        self.cache_dir = jax.config.jax_compilation_cache_dir
        return jax.devices()

    def drain(self):
        """A prove returns host data, but dispatch is asynchronous: wait for
        whatever the device still holds in flight before stopping a clock."""
        self.jax.block_until_ready(self.jax.live_arrays())

    @staticmethod
    def load_builder(cell: dict):
        """The configuration's circuit builder, found by name under
        circuits/."""
        name = cell["config"]["circuit"]["builder"]
        spec = importlib.util.spec_from_file_location(
            f"benchmark_circuit_{name}",
            os.path.join(cell["bench_dir"], "circuits", f"{name}.py"),
        )
        builder = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(builder)
        return builder

    def setup_key(self, cell: dict, trace_len: int) -> str:
        """What a kept setup depends on: the configuration as run, the
        request, the trace length, the circuit builder's file and every
        source file of the program. An edit to any of them makes a new key,
        and the run generates its setup again."""
        h = hashlib.sha256()
        h.update(json.dumps(
            [cell["config"]["circuit"], cell["config"]["proof_config"],
             cell["traffic"]["request"], int(trace_len)], sort_keys=True,
        ).encode())
        import boojum_tpu

        pkg = os.path.dirname(os.path.abspath(boojum_tpu.__file__))
        files = [os.path.join(cell["bench_dir"], "circuits",
                              f"{cell['config']['circuit']['builder']}.py")]
        for d, _sub, names in sorted(os.walk(pkg)):
            files += [os.path.join(d, n) for n in sorted(names) if n.endswith(".py")]
        for path in files:
            h.update(os.path.relpath(path, CHECKOUT).encode())
            with open(path, "rb") as f:
                h.update(f.read())
        return h.hexdigest()[:16]

    def synthesize(self, cell: dict, seed: int):
        """The witness from the seed: the builder is given the
        configuration's widths and the traffic mix's request."""
        config = cell["config"]
        builder = self.load_builder(cell)
        params = {**config["circuit"]["params"], **cell["traffic"]["request"]}
        self.asm = builder.build(params, seed).into_assembly()
        from boojum_tpu.prover import ProofConfig

        self.cfg = ProofConfig(**config["proof_config"])
        return int(self.asm.trace_len)

    def warm_library(self, workers: int, skip_setup: bool = False) -> list[str]:
        """Every kernel the program enumerates for this cell, through the
        program's own `precompile()`: lowered one after another on this
        thread, then compiled (a checkout's first run) or loaded from the
        cache (later runs) on a pool. Returns what failed. `skip_setup`
        leaves out the kernels only generate_setup runs (the library names
        them `setup:...`). Handing each kernel to the pool while the next is
        still being lowered was tried on the chip and was slower, cold and
        warm (PERF.md, call H)."""
        from boojum_tpu.prover import enumerate_kernels, precompile
        from boojum_tpu.utils.profiling import CompileLedger

        specs = [
            s for s in enumerate_kernels(self.asm, self.cfg)
            if not (skip_setup and s.name.startswith("setup:"))
        ]
        ledger = precompile(
            self.asm, self.cfg, max_workers=workers, ledger=CompileLedger(),
            specs=specs,
        )
        return [
            f"{e['name']}: {e['error']}"
            for e in ledger.to_dict()["entries"] if e.get("error")
        ]

    def generate_setup(self):
        from boojum_tpu.prover import generate_setup

        self.setup = generate_setup(self.asm, self.cfg)

    def save_setup(self, path: str):
        """Keep the setup (verification key, setup oracle and its tree) for
        the cell's later runs in this checkout, as a prover worker keeps its
        setup data on disk: it depends on the circuit, not on the witness."""
        import pickle

        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            pickle.dump(self.setup, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)

    def load_setup(self, path: str):
        """Only bytes that save_setup wrote, inside this checkout."""
        import pickle

        with open(path, "rb") as f:
            self.setup = pickle.load(f)
        self.drain()

    def prove(self):
        from boojum_tpu.prover import prove

        return prove(self.asm, self.setup, self.cfg)

    def proof_bytes(self, proof) -> bytes:
        return proof.to_json().encode()

    def proof_from_bytes(self, blob: bytes):
        from boojum_tpu.prover import Proof

        return Proof.from_json(blob.decode())

    def verify(self, proof) -> bool:
        from boojum_tpu.prover import verify

        return bool(verify(self.setup.vk, proof, self.asm.gates))

    def recorded_prove(self):
        """One prove under the program's flight recorder (never inside the
        window): returns (proof, counters)."""
        from boojum_tpu.utils import report

        with report.flight_recording(label="bench_warmup", sync=False) as rec:
            proof = self.prove()
            self.drain()
        return proof, dict(rec.metrics.to_dict()["counters"])

    def annotate_spans(self, trace_dir: str | None):
        """The program's spans reach the profiler as TraceAnnotations while
        this variable is set."""
        if trace_dir is None:
            os.environ.pop("BOOJUM_TPU_JAX_TRACE", None)
        else:
            os.environ["BOOJUM_TPU_JAX_TRACE"] = trace_dir

    def damage(self, blob: bytes) -> bytes:
        """The control: one opened value with its high 32 bits dropped."""
        d = json.loads(blob)
        for i, (c0, c1) in enumerate(d["values_at_z"]):
            if int(c0) >> 32:
                d["values_at_z"][i] = [int(c0) & 0xFFFFFFFF, c1]
                return json.dumps(d).encode()
        raise ValueError("no opened value has high bits to drop")

    def peak_bytes(self, devices) -> int:
        peaks = [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices
        ]
        return max(peaks) if peaks else 0
