"""What a Poseidon2-tree prove asks of the device, as one JSON line.

Run in a process of its own (`python tests/poseidon2_path_probe.py`): the
shared 2^10 circuit is proved once to compile and cache, then once under
the flight recorder and the profiler. Printed: the jitted programs the host
called, in order (the CPU runtime's `PjitFunction(<name>)` events), the
recorder's upload, `merkle.*` and `ntt.*` counters, and the number of live
device arrays after `import boojum_tpu` and after the proves are dropped.
`tests/data/poseidon2_path_parent.json` is this script's line at the commit
before the Blake2s tree hasher; `tests/test_poseidon2_path_unchanged.py`
holds every later tree to it.
"""

import gc
import glob
import json
import os
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import jax  # noqa: E402

import boojum_tpu  # noqa: E402,F401

PREFIX = "PjitFunction("


def _programs(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    )
    events = [
        (e.start_ns, e.name[len(PREFIX):-1])
        for pl in ProfileData.from_file(path).planes
        for ln in pl.lines for e in ln.events if e.name.startswith(PREFIX)
    ]
    return [name for _start, name in sorted(events)]


def main():
    out = {"live_after_import": len(jax.live_arrays())}
    from proving import small_parts

    from boojum_tpu.prover import prove
    from boojum_tpu.utils import report

    asm, setup, config = small_parts()
    prove(asm, setup, config)  # every shape compiled, every input cached
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            with report.flight_recording(label="probe", sync=False) as rec:
                proof = prove(asm, setup, config)
        finally:
            jax.profiler.stop_trace()
        out["programs"] = _programs(d)
    counters = report.build_report(rec)["metrics"]["counters"]
    out["counters"] = {
        k: v for k, v in sorted(counters.items())
        if k.startswith(("merkle.", "ntt.", "transfer.h2d_"))
    }
    del proof, rec, counters
    gc.collect()
    out["live_after_prove"] = len(jax.live_arrays())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
