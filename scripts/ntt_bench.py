"""Quick NTT microbench on the ambient JAX backend (a local TPU, or CPU).

Usage: python scripts/ntt_bench.py [log_n] [cols] [reps]
Prints XLA vs MXU throughput for fwd+inv pairs.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from boojum_tpu.field import gl
from boojum_tpu.ntt import ntt as ntt_mod
from boojum_tpu.ntt import mxu_ntt

log_n = int(sys.argv[1]) if len(sys.argv) > 1 else 16
cols = int(sys.argv[2]) if len(sys.argv) > 2 else 64
reps = int(sys.argv[3]) if len(sys.argv) > 3 else 4

rng = np.random.default_rng(0)
a = jnp.asarray(rng.integers(0, gl.P, size=(cols, 1 << log_n), dtype=np.uint64))
n_elems = cols * (1 << log_n)


def run(tag, fwd, inv):
    x = fwd(a)
    x = inv(x)
    jax.block_until_ready(x)
    t0 = time.perf_counter()
    x = a
    for _ in range(reps):
        x = inv(fwd(x))
    jax.block_until_ready(x)
    dt = time.perf_counter() - t0
    eps = 2 * reps * n_elems / dt
    print(f"{tag}: {dt/reps*1e3:.2f} ms/pair-rep, {eps:.3e} elems/s")
    return x, eps


want, eps_xla = run(
    "xla",
    lambda v: ntt_mod.fft_natural_to_bitreversed_xla(v),
    lambda v: ntt_mod.ifft_bitreversed_to_natural_xla(v),
)
got, eps_mxu = run(
    "mxu",
    lambda v: mxu_ntt.fft_natural_to_bitreversed(v),
    lambda v: mxu_ntt.ifft_bitreversed_to_natural(v),
)
print("match:", bool(jnp.array_equal(want, got)), "speedup:", round(eps_mxu / eps_xla, 2))
