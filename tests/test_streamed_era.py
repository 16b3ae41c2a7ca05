"""The streamed commit on limb planes above the single-kernel ceiling, and a
whole streamed prove at the Era settings (ISSUE 39: the configuration
`keccak256-era-512k`, the first cell whose commits stream).

Above 2^16 rows on the chip the forward NTT is a device program of its own
that no jit may hold (`limb_ntt._hybrid_fwd_p`), so the streamed routines
are host drivers around it there: the commit's block transform
(`streaming.lde_block_cols_p`), round 5's single-column regenerations
(`resident.cols_from_mono_p`) and the query phase's leaf-value gather
(`resident.stream_gather_p`). Each is held here to the plain reference
`benchmark/tools/streamed_commit_reference.py` (numpy and Python ints,
nothing of the prover's, the transforms' or the trees' code):

- on the CPU's own path at 2^6-2^7 rows, whole trees;
- under `tests/test_fused_forward_ntt.py`'s lowered ceiling (2^14, the
  kernel in interpret mode) at 2^17 rows, so that the own-program
  transform with its one LEADING outer stage is what runs: 2^17 rows stand
  for the cell's 2^19;
- through a whole prove at the Era widths and settings with the commits
  streamed by a lowered byte threshold and the setup under it, as the cell
  runs them.
"""

import importlib.util
import json
import logging
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from boojum_tpu.field import gl, limbs
from boojum_tpu.merkle import node_layers_planes
from boojum_tpu.ntt import limb_ntt as LN
from boojum_tpu.ntt import ntt as NTT
from boojum_tpu.prover import resident as RES
from boojum_tpu.prover import streaming as ST
from boojum_tpu.utils import metrics
from proving import environ, prove_recorded
from test_fused_forward_ntt import CEILING, lowered_ceiling  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(
    "streamed_commit_reference",
    os.path.join(BENCH, "tools", "streamed_commit_reference.py"),
)


def _rand(shape, seed):
    a = np.random.default_rng(seed).integers(
        0, gl.P, size=shape, dtype=np.uint64
    )
    a[..., 0] = gl.P - 1  # max limbs through every butterfly
    return a


def _planes(a):
    lo, hi = limbs.split_np(np.asarray(a, np.uint64))
    return jnp.asarray(lo), jnp.asarray(hi)


def _join(p):
    return limbs.join_np(np.asarray(p[0]), np.asarray(p[1]))


def _rows(p):
    return [tuple(int(v) for v in row) for row in _join(p)]


@pytest.fixture
def counters():
    reg = metrics.MetricsRegistry()
    previous = metrics.install_registry(reg)
    yield reg.counters
    metrics.install_registry(previous)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 8 columns in chunks of 4: a few columns then walk every
    branch the cell's 155 walk (whole blocks, a ragged last block, a block
    of several forward dispatches, a chunk that is the whole stack)."""
    monkeypatch.setattr(ST, "COL_BLOCK", 8)

    def budget(n, L):
        monkeypatch.setattr(NTT, "_NTT_CHUNK_BUDGET", 4 * n * 8 * L)

    return budget


@pytest.fixture
def forward_calls(monkeypatch):
    """Every call of the own-program transform, and none under a trace."""
    calls = []
    real = LN._hybrid_fwd_p

    def spy(p, *args, **kwargs):
        assert not isinstance(p[0], jax.core.Tracer), (
            "a forward transform above the ceiling was traced into a jit"
        )
        calls.append(p[0].shape)
        return real(p, *args, **kwargs)

    monkeypatch.setattr(LN, "_hybrid_fwd_p", spy)
    return calls


@pytest.fixture
def compiled_names():
    """Names of the programs jax compiles inside the block."""
    names = []

    class Handler(logging.Handler):
        def emit(self, record):
            m = re.match(r"Compiling jit\((\w+)\)", record.getMessage())
            if m:
                names.append(m.group(1))

    handler = Handler()
    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(handler)
    with jax.log_compiles(True):
        yield names
    logger.removeHandler(handler)


def _library_programs():
    """Names of the jitted programs the streamed path's modules define."""
    from boojum_tpu import merkle
    from boojum_tpu.prover import prover as P

    names = set()
    for mod in (ST, RES, LN, P, merkle):
        for value in vars(mod).values():
            if type(value).__name__ == "PjitFunction":
                names.add(value.__name__)
    for pair in (LN._NTT_FORWARD, LN._LDE_FORWARD, LN._COSET_EVAL_FORWARD):
        names.update(f.__name__ for f in pair)
    return names


# -- the reference itself -----------------------------------------------------


def test_reference_field_and_transform_against_python_ints():
    """The reference's own arithmetic: products, sums and differences at
    the limbs' extremes against Python ints, and the radix-2 transform
    against Horner's rule at every point of a small domain."""
    edge = np.array(
        [0, 1, gl.P - 1, (1 << 32) - 1, 1 << 32, 1 << 63, gl.P - (1 << 32)],
        dtype=np.uint64,
    )
    a = np.concatenate([np.repeat(edge, len(edge)), _rand((200,), 1)])
    b = np.concatenate([np.tile(edge, len(edge)), _rand((200,), 2)])
    for got, op in (
        (REF.mul_mod(a, b), lambda x, y: x * y),
        (REF.add_mod(a, b), lambda x, y: x + y),
        (REF.sub_mod(a, b), lambda x, y: x - y),
    ):
        assert [int(v) for v in got] == [
            op(int(x), int(y)) % gl.P for x, y in zip(a, b)
        ]
    mono = _rand((3, 16), 3)
    assert REF.P == gl.P and REF.root_of_unity(5) == gl.omega(5)
    assert np.array_equal(REF.intt(REF.ntt(mono)), mono)
    assert np.array_equal(
        REF.lde_values(mono, 2).T, REF.evaluate_rows(mono, 2, range(32))
    )


# -- (a) the streamed commit's cap against the reference's --------------------


@pytest.mark.parametrize("cols,log_n", [(8, 6), (13, 6), (32, 6), (37, 7)])
def test_streamed_cap_equals_the_reference_tree(cols, log_n, counters):
    """Whole trees on the CPU's own path, for column counts that are and
    are not multiples of the sponge's rate and of COL_BLOCK."""
    L, cap = 2, 4
    mono = _rand((cols, 1 << log_n), 10 + cols)
    digests = ST.streamed_leaf_digests_blocks_p(_planes(mono), L)
    got = _rows(node_layers_planes(digests, cap)[-1])
    assert got == REF.commit(mono, L, cap)
    assert counters["stream.lde_columns.commit"] == cols
    assert counters["stream.double_buffered_blocks"] == -(-cols // ST.COL_BLOCK)


def test_commit_above_the_ceiling_every_leaf_value(
    lowered_ceiling, small_blocks, forward_calls, counters  # noqa: F811
):
    """2^(CEILING + 3) rows, 11 columns in blocks of 8 and chunks of 4:
    every leaf value of every block against the reference's transform, the
    digests of a seeded sample of leaves against the reference's sponge
    (the device's absorb over the sampled rows alone), and the counters:
    one leading XLA stage and two fused ones a column transform."""
    log_n, L, cols = CEILING + 3, 2, 11
    n = 1 << log_n
    small_blocks(n, L)
    assert ST.block_chunk_sizes(cols, n, L) == [4, 4, 3]
    mono = _rand((cols, n), 20)
    want = REF.lde_values(mono, L)  # (cols, N), leaf order
    mono_p = _planes(mono)
    blocks = {
        i: ST.lde_block_cols_p(mono_p, i, min(8, cols - i), L)
        for i in range(0, cols, 8)
    }
    for i, blk in blocks.items():  # column-major: leaves along the lanes
        assert np.array_equal(_join(blk), want[i : i + 8]), f"block {i}"
    assert forward_calls == [(4, n), (4, n), (3, n)]
    assert counters["ntt.leading_outer_stages"] == 1 * cols * L
    assert counters["ntt.fused_outer_stages"] == 2 * cols * L
    sample = np.random.default_rng(21).integers(0, n * L, 24)
    sample[:2] = [0, n * L - 1]
    z = jnp.zeros((12, len(sample)), jnp.uint32)
    state = (z, z)
    for i, blk in blocks.items():
        state = ST._absorb_cols_cm_p(
            state, (blk[0][:, sample], blk[1][:, sample])
        )
    assert _rows(ST._absorb_cols_digests_p(state)) == [
        REF.leaf_digest(want[:, r]) for r in sample
    ]


# -- (b), (c) the regenerations of DEEP and of the queries ---------------------


def test_regenerations_above_the_ceiling_equal_the_reference(
    lowered_ceiling, small_blocks, forward_calls, counters  # noqa: F811
):
    """`stream_gather_p`, `cols_from_mono_p` and DEEP's blocks at
    2^(CEILING + 3) rows: the reference's leaf values at the opened
    indices and in the opened columns; every forward transform was
    dispatched from the host, none traced."""
    log_n, L, cols = CEILING + 3, 2, 11
    n = 1 << log_n
    small_blocks(n, L)
    mono = _rand((cols, n), 30)
    want = REF.lde_values(mono, L)
    source = ST.MonomialPlanesSource(_planes(mono), L)
    idx = np.random.default_rng(31).integers(0, n * L, 9)
    idx[:2] = [0, n * L - 1]
    got = RES.stream_gather_p(source, jnp.asarray(idx))
    assert np.array_equal(_join(got), want[:, idx])
    assert forward_calls == [(4, n), (4, n), (3, n)]
    del forward_calls[:]
    picked = (0, 1, 5, 6, 7, 10)
    cols_p = RES.cols_from_mono_p(source.mono, picked, L)
    assert np.array_equal(_join(cols_p), want[list(picked)])
    assert forward_calls == [(4, n), (2, n)]
    del forward_calls[:]
    seen = 0
    for flat, off in RES.deep_source_blocks_p([source], 1 << 20):
        b = flat[0].shape[0]
        assert np.array_equal(_join(flat), want[off : off + b])
        seen += b
    assert seen == cols and forward_calls == [(4, n), (4, n), (3, n)]
    assert counters["stream.lde_columns.queries"] == cols
    assert counters["stream.lde_columns.deep"] == len(picked) + cols


def test_library_lists_what_the_streamed_routines_dispatch(
    lowered_ceiling, small_blocks, compiled_names, monkeypatch  # noqa: F811
):
    """Above the ceiling, every program of the library's own that the
    three routines compile is one `stream_kernel_specs` /
    `cols_from_mono_kernel_specs` lists."""
    log_n, L, cols = CEILING + 1, 2, 11
    n = 1 << log_n
    small_blocks(n, L)
    # programs compiled by an earlier test of this process are not
    # compiled again: start the streamed routines' own from nothing
    for fn in (ST._lde_block_cols_take_p, ST._lde_block_cols_join_p,
               ST._stream_gather_block_p, ST._stream_gather_join_p,
               RES._deep_cols_take_p):
        fn.clear_cache()
    mono_p = _planes(_rand((cols, n), 40))
    source = ST.MonomialPlanesSource(mono_p, L)
    picked = (0, 1, 5, 6, 7, 10)
    for i in range(0, cols, 8):
        ST.lde_block_cols_p(mono_p, i, min(8, cols - i), L)
    RES.stream_gather_p(source, jnp.asarray(np.arange(9)))
    RES.cols_from_mono_p(mono_p, picked, L)
    listed = {
        fn.__name__
        for _name, fn, _args in RES.stream_kernel_specs(cols, n, L, 9)
        + RES.cols_from_mono_kernel_specs("s2", cols, n, L, picked)
    }
    ran = set(compiled_names) & _library_programs()
    assert ran and ran <= listed, sorted(ran - listed)
    assert {"_lde_block_cols_take_p", "_lde_block_cols_join_p",
            "_lde_planes_hybrid_fused_p", "_stream_gather_block_p",
            "_stream_gather_join_p", "_deep_cols_take_p"} <= ran
    for _name, fn, args in RES.stream_kernel_specs(cols, n, L, 9):
        jax.eval_shape(fn, *args)  # each listed program takes its arguments


@pytest.mark.parametrize("log_n,own", [(16, False), (19, True)])
def test_streamed_library_at_the_cell_sizes(monkeypatch, log_n, own):
    """What the library lists for a streamed Era oracle (155 columns) on the
    chip: one host-driven form at every size, a block of 32 columns in one
    transform at 2^16 rows and in chunks of 16 columns at 2^19."""
    monkeypatch.setattr(LN, "_mxu_ntt_ready", lambda n, ctx: True)
    n, L = 1 << log_n, 2
    assert LN.forward_is_own_program(n) == own
    names = [s[0] for s in RES.stream_kernel_specs(155, n, L, 100)]
    assert len(set(names)) == len(names)
    assert not any("stream_gather_limbres" in x for x in names)
    if not own:
        assert ST.block_chunk_sizes(155, n, L) == [32] * 4 + [27]
        for want in ("lde_block_cols_take_limbres_b155_c32",
                     "lde_block_cols_take_limbres_b155_c27",
                     "lde_block_cols_join_limbres_b32",
                     "stream_gather_block_limbres_c27",
                     "stream_gather_join_limbres_b155"):
            assert want in names, want
        assert any(x.startswith("stream:lde") for x in names)
        return
    assert ST.block_chunk_sizes(155, n, L) == [16, 16] * 4 + [16, 11]
    for want in ("lde_block_cols_take_limbres_b155_c16",
                 "lde_block_cols_take_limbres_b155_c11",
                 "lde_block_cols_join_limbres_b32",
                 "lde_block_cols_join_limbres_b27",
                 f"stream:lde_hybrid_limbres_b16_n{n}_L2:outer",
                 f"stream:lde_hybrid_limbres_b11_n{n}_L2:fused",
                 "stream_gather_block_limbres_c16",
                 "stream_gather_join_limbres_b155"):
        assert want in names, want


# -- what the chip forced: the quotient's inverse transform at 2^22 -----------


def test_quotient_interpolation_in_two_programs(
    lowered_ceiling, compiled_names, monkeypatch  # noqa: F811
):
    """Past `limb_ntt.INVERSE_FUSED_OUTER_STAGES` outer stages the
    quotient's inverse transform is two programs, the per-block inverse
    kernels and the outer DIT stages with the rows (as ONE program it did
    not return at 2^22 on the v5e: PERF.md, PR 39); equal to the u64
    interpolation, and listed. Ceiling 2^14, a quotient domain of 2^16 and
    a limit of one outer stage stand for 2^16, 2^22 and five."""
    from boojum_tpu.ntt import mxu_ntt
    from boojum_tpu.prover import prover as P

    inverse = mxu_ntt._ifft_planes
    monkeypatch.setattr(
        mxu_ntt, "_ifft_planes", lambda p, log_n, _: inverse(p, log_n, True)
    )
    Q, n = 4, 1 << CEILING
    parts = [_rand((n,), 50 + i) for i in range(2 * Q)]
    T0, T1 = parts[:Q], parts[Q:]
    want = P._quotient_interp(
        tuple(map(jnp.asarray, T0)), tuple(map(jnp.asarray, T1)), Q, n
    )
    planes = [tuple(map(_planes, T)) for T in (T0, T1)]
    assert not LN.inverse_is_two_programs(Q * n)  # 2 stages of 5 allowed
    names = [s[0] for s in RES.quotient_interp_kernel_specs(Q, n)]
    assert names == ["quotient_interp_limbres"]
    monkeypatch.setattr(LN, "INVERSE_FUSED_OUTER_STAGES", 1)
    assert LN.inverse_is_two_programs(Q * n)
    got = RES.quotient_interp_p(*planes, Q, n)
    assert np.array_equal(_join(got), np.asarray(want))
    assert "_quotient_interp_p" not in compiled_names
    specs = RES.quotient_interp_kernel_specs(Q, n)
    assert [s[0] for s in specs] == [
        "quotient_interp_limbres:kernels", "quotient_interp_limbres:outer"
    ]
    assert {s[1].__name__ for s in specs} <= set(compiled_names)
    blocks = jax.eval_shape(specs[0][1], *specs[0][2])
    assert jax.eval_shape(specs[1][1], blocks, Q, n)[0].shape == (2 * Q, n)
    # the cell's own sizes: 2^19 rows under 8 cosets pass the limit, 2^18 do not
    monkeypatch.setattr(LN, "INVERSE_FUSED_OUTER_STAGES", 5)
    monkeypatch.setattr(mxu_ntt, "MAX_LOG_N", 16)
    assert [LN.inverse_is_two_programs(8 << k) for k in (16, 18, 19)] == [
        False, False, True
    ]
    RES._quotient_interp_kernels_p.clear_cache()  # traced under the ceiling
    RES._quotient_interp_outer_p.clear_cache()


# -- (d), (e) a whole streamed prove at the Era settings ----------------------


def _era_config():
    with open(os.path.join(BENCH, "configs", "keccak256-era-512k.json")) as f:
        return json.load(f)


# the small circuit's storages are 399 columns x 2^11 x 8 B = 6.5 MB and its
# setup oracle's 167 x 2^11 x 8 B = 2.7 MB: between them, the commits of a
# prove stream and the setup stays materialized, as in the cell
STREAM_BETWEEN = {"BOOJUM_TPU_STREAM_LDE": str(4 << 20)}


@pytest.fixture(scope="module")
def era_parts():
    """(assembly, setup, config) of tests/test_keccak_era.py's 2^10-row
    circuit on the configuration's widths, set up under the threshold the
    streamed prove runs under."""
    from boojum_tpu.prover import ProofConfig, generate_setup, precompile
    from test_keccak_era import _small_assembly

    asm = _small_assembly()
    cfg = ProofConfig(**_era_config()["proof_config"])
    with environ(STREAM_BETWEEN):
        precompile(asm, cfg, max_workers=os.cpu_count() or 4)
        setup = generate_setup(asm, cfg)
    assert setup.setup_lde is not None  # decided alone, and materialized
    return asm, setup, cfg


def test_configuration_is_keccak256_era_at_half_the_golden_trace():
    with open(os.path.join(BENCH, "configs", "keccak256-era.json")) as f:
        era = json.load(f)
    c = _era_config()
    for key in ("circuit", "proof_config", "source_keys"):
        assert c[key] == era[key], key
    assert c["trace_len"] == 1 << 19 and c["reduced"] == ["trace_len"]
    assert len(c["source"]) < 200


def test_streamed_prove_at_era_settings(era_parts, compiled_names):
    """The commits of rounds 1-3 streamed, the setup materialized: proof
    bytes equal to the materialized prove's, accepted by the host
    verifier, three streamed commits, no interior conversion, the
    regenerations counted; and the library lists every program of its own
    that the prove compiled."""
    from boojum_tpu.prover import enumerate_kernels, verify

    asm, setup, cfg = era_parts
    with environ(STREAM_BETWEEN):
        proof, rep = prove_recorded("era_streamed", parts=era_parts)
        listed = {s.fn.__name__ for s in enumerate_kernels(asm, cfg)}
    # the one program the library leaves to dispatch by design: the fused
    # query gather, whose shapes follow the drawn indices
    ran = (set(compiled_names) & _library_programs()) - {"_gather_flat_fused"}
    assert ran <= listed, sorted(ran - listed)
    plain, plain_rep = prove_recorded("era_materialized", parts=era_parts)
    assert proof.to_json() == plain.to_json()
    assert verify(setup.vk, proof, asm.gates)
    c, c0 = rep["metrics"]["counters"], plain_rep["metrics"]["counters"]
    assert c["merkle.streamed_commits"] == 3
    assert c0.get("merkle.streamed_commits", 0) == 0
    assert c.get("limb.splits", 0) == 0 and c.get("limb.joins", 0) == 0
    B_wit, S, B_q = 155, 62, 16
    assert c["stream.lde_columns.commit"] == B_wit + S + B_q
    assert c["stream.lde_columns.queries"] == B_wit + S + B_q
    # DEEP: every streamed column once, the 2 + 2 x 9 shifted and lookup
    # columns of stage 2 and the public input's column once more
    assert c["stream.lde_columns.deep"] == (
        B_wit + S + B_q + 20 + len(asm.public_inputs)
    )
    # round 3 reads only the setup's two committed cosets: the streamed
    # groups kept no storage (prover.coset_is_committed)
    assert c["quotient.coset_evals_reused"] == 2
    assert c0["quotient.coset_evals_reused"] == 2 * 3
    assert c["quotient.sweep_barrier_stride"] == 0  # XLA:CPU reports no limit
    assert c["prover.input_caches_dropped"] == 0  # nor a reason to drop them
    spans = json.dumps(rep["spans"])
    for name in ("stream.commit", "stream.deep_regen", "stream.query_regen"):
        assert f'"{name}"' in spans, name


def test_streamed_witness_openings_equal_the_reference(era_parts):
    """The opened witness rows of a streamed proof against the reference
    fed the witness's VALUES (its own inverse transform, its own
    evaluation, its own sponge), and each leaf's digest walked up the
    proof's path to the proof's cap: the comparison the chip tool
    `benchmark/tools/streamed_openings_check.py` makes at 2^19 rows."""
    check = _load(
        "streamed_openings_check",
        os.path.join(BENCH, "tools", "streamed_openings_check.py"),
    )
    asm, setup, cfg = era_parts
    with environ(STREAM_BETWEEN):
        proof, _rep = prove_recorded("era_streamed_openings", parts=era_parts)
    found = check.check_witness_openings(asm, cfg, json.loads(proof.to_json()))
    assert found == {"queries": cfg.num_queries, "columns": 155,
                     "values_differing": 0, "paths_off_the_cap": 0}
    # and the comparison is live: an opened value off by one is found, and
    # so is a path that is not the leaf's
    damaged = json.loads(proof.to_json())
    leaf = damaged["queries"][3]["witness"]["leaf_values"]
    leaf[7] = (int(leaf[7]) + 1) % gl.P
    path = damaged["queries"][5]["witness"]["path"]
    path[0] = [(int(path[0][0]) + 1) % gl.P, *path[0][1:]]
    found = check.check_witness_openings(asm, cfg, damaged)
    assert found["values_differing"] == 1 and found["paths_off_the_cap"] == 1


# -- the device-input caches of a streamed prove: kept or dropped by plan -------


class _Chip:
    """A device whose allocator reports the v5e's limit."""

    def __init__(self, in_use):
        self._stats = {"bytes_limit": 16_909_336_064, "bytes_in_use": in_use}

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("in_use", [0, 16_000_000_000])
@pytest.mark.parametrize("label,log_n,fit", [
    ("keccak256-era-512k: 4.3 GB planned, 6.3 GB to queue, 3.2 GB a coset",
     19, True),
    ("the golden trace: 8.7 GB planned, 4.1 GB to queue, 6.5 GB a coset",
     20, False),
])
def test_input_caches_stay_or_go_by_plan(
    monkeypatch, counters, label, log_n, fit, in_use
):
    """The Era geometry's streamed prove keeps its witness, sigma and table
    caches at 2^19 rows and drops them at 2^20, from the allocator's limit
    and the shapes alone: what the device has in use at the moment decides
    nothing (Tentpole 2: a choice that does not follow the host's pace)."""
    from boojum_tpu.prover import prover as P

    monkeypatch.setattr(jax, "local_devices", lambda: [_Chip(in_use)])
    n = 1 << log_n
    ws = P._sweep_working_set_bytes(155 + 167 + 62 + 2, n)
    setup_storage = 8 * 167 * 2 * n
    # 130 + 25 witness and lookup columns, 130 sigmas, 3 table columns, the
    # multiplicities: what the cell's caches held on the chip to the column
    cache_cols = {"witness_planes": 155, "table_stack_planes": 3,
                  "mult_planes": 1, "sigma_planes": 130}

    class Host:
        pass

    asm, setup = Host(), Host()
    planes = {k: (np.zeros((c, n), np.uint32),) * 2 for k, c in cache_cols.items()}
    asm._dev_cache = {k: planes[k] for k in list(cache_cols)[:3]}
    asm._dev_cache["xs_h"] = np.zeros(n, np.uint64)  # not an input cache
    setup._dev_cache = {"sigma_planes": planes["sigma_planes"]}
    keys = (tuple(list(cache_cols)[:3]), ("sigma_planes",))
    assert P._input_caches_fit_by_plan(ws, setup_storage, 289 * 8 * n) == fit
    P._drop_input_caches_if_short(asm, setup, keys, ws, setup_storage)
    assert counters["prover.input_caches_dropped"] == (0 if fit else 1), label
    assert set(asm._dev_cache) == ({*keys[0], "xs_h"} if fit else {"xs_h"})
    assert set(setup._dev_cache) == ({"sigma_planes"} if fit else set())
    # a backend that reports no limit keeps them
    monkeypatch.setattr(jax, "local_devices", lambda: [_Chip(0)])
    monkeypatch.setattr(_Chip, "memory_stats", lambda self: None)
    assert P._input_caches_fit_by_plan(ws, setup_storage, 289 * 8 * n)
