"""Native (C++) host code: build + ctypes bindings. One library, one rule.

It holds the witness-resolution engine, the counterpart of the reference's
compiled resolver runtime (the Rust `MtCircuitResolver` machinery,
/root/reference/src/dag/), and the Poseidon2 sponge steps under the PROVER's
transcript (`poseidon2_permute`, `poseidon2_absorb`; PR 38: the resolver's
permutation, whose constants `get_lib` registers once from
`hashes/poseidon2_params`). The shared library is built on demand with g++
and cached next to the source; where no compiler is available or
`BOOJUM_TPU_NO_NATIVE` is set `get_lib()` returns None, and the framework
falls back to the pure-python resolver and the pure-python permutation.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "resolver.cpp")
_LIB = os.path.join(_HERE, "libboojum_resolver.so")

_lib = None
_tried = False


def _build() -> bool:
    try:
        src_mtime = os.path.getmtime(_SRC)
        if os.path.exists(_LIB) and os.path.getmtime(_LIB) >= src_mtime:
            return True
        r = subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", _LIB + ".tmp", _SRC],
            capture_output=True,
            timeout=240,
        )
        if r.returncode != 0:
            return False
        os.replace(_LIB + ".tmp", _LIB)
        return True
    except Exception:
        return False


def get_lib():
    """The loaded library, or None when unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("BOOJUM_TPU_NO_NATIVE"):
        return None
    if not _build():
        return None
    try:
        lib = ctypes.CDLL(_LIB)
    except OSError:
        return None
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    lib.register_table.argtypes = [
        ctypes.c_int64, u64p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
    ]
    lib.register_table.restype = ctypes.c_int
    lib.table_multiplicities.argtypes = [ctypes.c_int64, i64p]
    lib.table_multiplicities.restype = u32p
    lib.reset_tables.argtypes = []
    lib.register_poseidon2.argtypes = [u64p, u64p]
    lib.execute_tape.argtypes = [
        u64p, ctypes.c_uint64,
        i64p, ctypes.c_int64,
        u64p, i64p,
        i64p, i64p,
        i64p, i64p,
    ]
    lib.execute_tape.restype = ctypes.c_int64
    lib.poseidon2_permute.argtypes = [u64p]
    lib.poseidon2_permute.restype = ctypes.c_int
    lib.poseidon2_absorb.argtypes = [u64p, u64p, ctypes.c_int64]
    lib.poseidon2_absorb.restype = ctypes.c_int
    # one-time poseidon2 constants
    from ..hashes import poseidon2_params as p2

    rc = np.array(p2.ALL_ROUND_CONSTANTS, dtype=np.uint64)
    diag = np.array(p2.M_I_DIAGONAL, dtype=np.uint64)
    lib.register_poseidon2(
        rc.ctypes.data_as(u64p), diag.ctypes.data_as(u64p)
    )
    _lib = lib
    return _lib


def _as_u64p(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def _as_i64p(arr):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


_next_table_slot = [1]  # process-global: each CS's tables get fresh slots


class NativeTape:
    """Typed-op tape accumulated during synthesis, flushed in batches.

    Local (per-CS) table ids map to process-global C-engine slots so
    multiple constraint systems in one process never share multiplicity
    counters."""

    def __init__(self, lib):
        self.lib = lib
        self.kinds: list[int] = []
        self.params: list[int] = []
        self.param_off: list[int] = [0]
        self.ins: list[int] = []
        self.in_off: list[int] = [0]
        self.outs: list[int] = []
        self.out_off: list[int] = [0]
        self._slot_of: dict[int, int] = {}

    def __len__(self):
        return len(self.kinds)

    def append(self, kind: int, params, ins, outs):
        self.kinds.append(kind)
        self.params.extend(params)
        self.param_off.append(len(self.params))
        self.ins.extend(ins)
        self.in_off.append(len(self.ins))
        self.outs.extend(outs)
        self.out_off.append(len(self.outs))

    def ensure_table(self, table_id: int, table):
        if table_id in self._slot_of:
            return
        slot = _next_table_slot[0]
        _next_table_slot[0] += 1
        content = np.ascontiguousarray(table.content, dtype=np.uint64)
        rc = self.lib.register_table(
            slot, _as_u64p(content), len(content),
            table.width, table.num_keys,
        )
        assert rc == 0
        self._slot_of[table_id] = slot

    def has_table(self, table_id: int) -> bool:
        return table_id in self._slot_of

    def slot_of(self, table_id: int) -> int:
        return self._slot_of[table_id]

    def multiplicities_of(self, table_id: int):
        slot = self._slot_of.get(table_id)
        if slot is None:
            return None
        return self.multiplicities(slot)

    def take_snapshot(self):
        """Detach the accumulated ops as dense arrays (the tape resets).

        Returns None when empty, else an opaque snapshot consumed by
        `run_snapshot` — the split lets a worker thread execute one batch
        while synthesis keeps appending to the (fresh) tape."""
        if not self.kinds:
            return None
        snap = (
            np.array(self.kinds, dtype=np.int64),
            np.array(self.params, dtype=np.uint64),
            np.array(self.param_off, dtype=np.int64),
            np.array(self.ins, dtype=np.int64),
            np.array(self.in_off, dtype=np.int64),
            np.array(self.outs, dtype=np.int64),
            np.array(self.out_off, dtype=np.int64),
            self.outs,
            self.kinds,
        )
        self.kinds = []
        self.params = []
        self.param_off = [0]
        self.ins = []
        self.in_off = [0]
        self.outs = []
        self.out_off = [0]
        return snap

    def run_snapshot(self, values: np.ndarray, snap) -> list:
        """Execute a snapshot against the arena; returns the out places.

        The ctypes call releases the GIL, so running this on a worker
        thread overlaps native resolution with python-side synthesis. A
        failed batch must never be re-executed (ops before the failure
        already ran — a second pass would double-bump lookup
        multiplicities); snapshots are one-shot by construction."""
        kinds, params, p_off, ins, i_off, outs, o_off, out_places, kl = snap
        rc = self.lib.execute_tape(
            _as_u64p(values), len(values),
            _as_i64p(kinds), len(kinds),
            _as_u64p(params), _as_i64p(p_off),
            _as_i64p(ins), _as_i64p(i_off),
            _as_i64p(outs), _as_i64p(o_off),
        )
        if rc != 0:
            raise RuntimeError(
                f"native resolver op (kind {kl[-int(rc) - 1]}) failed — "
                "lookup miss, oversized key, or unregistered table"
            )
        return out_places

    def execute(self, values: np.ndarray) -> list:
        """Run all pending ops against the arena; returns the out places."""
        snap = self.take_snapshot()
        if snap is None:
            return []
        return self.run_snapshot(values, snap)

    def multiplicities(self, table_id: int) -> np.ndarray:
        rows = ctypes.c_int64()
        ptr = self.lib.table_multiplicities(table_id, ctypes.byref(rows))
        return np.ctypeslib.as_array(ptr, shape=(rows.value,)).copy()


OP_CONST = 0
OP_FMA = 1
OP_REDUCTION = 2
OP_SPLIT = 3
OP_U32_ADD = 4
OP_U32_SUB = 5
OP_TRIADD = 6
OP_U32_FMA = 7
OP_BYTE_TRIADD = 8
OP_POSEIDON2 = 9
OP_LOOKUP = 10
OP_LOOKUP_BUMP = 11
