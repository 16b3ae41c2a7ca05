"""Symbolic gate-program capture — the offload-synthesizer seam.

Counterpart of `/root/reference/src/gpu_synthesizer/` (856 LoC):
`GpuSynthesizerFieldLike` (mod.rs:201) runs each gate's constraint evaluator
once over a fake field whose "values" are symbolic indices, recording every
arithmetic op as a `Relation` (mod.rs:169-190) so a device backend can replay
constraint evaluation without re-tracing the evaluator.

Here the same contract face (`zero/one/constant/add/sub/mul/neg/double`)
records a straight-line SSA program per gate. Two uses:
- inspection/debug: a portable, serializable description of every gate's
  constraint circuit (op counts, degree audits);
- replay: `GateProgram.evaluate_rows` interprets the program over any ops
  context (scalars or whole device arrays), byte-equivalent to running the
  evaluator directly — this is the seam a custom fused-kernel backend
  (e.g. a Pallas gate-sweep generator) consumes.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ..field import gl
from .gates.base import RowView, TermsCollector


@dataclass(frozen=True)
class Sym:
    """A symbolic value: an SSA slot index."""

    idx: int


@dataclass
class GateProgram:
    """Straight-line program of one gate instance's constraint evaluation.

    Inputs are addressed as ('v', i) / ('w', i) / ('c', i) loads; every op is
    (opcode, dst_slot, src_a, src_b) with constants inlined by value.
    """

    gate_name: str = ""
    loads: list = field(default_factory=list)  # (slot, kind, index)
    consts: list = field(default_factory=list)  # (slot, value)
    ops: list = field(default_factory=list)  # (op, dst, a_slot, b_slot)
    terms: list = field(default_factory=list)  # slot per quotient term
    num_slots: int = 0

    # -- replay ------------------------------------------------------------

    def evaluate(self, ops_ctx, row: RowView):
        """Interpret over any field-like ops context + row view; returns the
        term values (same results as gate.evaluate, by construction)."""
        slots = [None] * self.num_slots
        for slot, kind, index in self.loads:
            slots[slot] = (
                row.v(index) if kind == "v"
                else row.w(index) if kind == "w"
                else row.c(index)
            )
        for slot, value in self.consts:
            slots[slot] = ops_ctx.constant(value)
        for op, dst, a, b in self.ops:
            if op == "add":
                slots[dst] = ops_ctx.add(slots[a], slots[b])
            elif op == "sub":
                slots[dst] = ops_ctx.sub(slots[a], slots[b])
            elif op == "mul":
                slots[dst] = ops_ctx.mul(slots[a], slots[b])
            elif op == "neg":
                slots[dst] = ops_ctx.neg(slots[a])
            elif op == "double":
                slots[dst] = ops_ctx.double(slots[a])
            else:
                raise ValueError(op)
        return [slots[t] for t in self.terms]

    def stats(self) -> dict:
        from collections import Counter

        c = Counter(op for (op, *_rest) in self.ops)
        return {
            "gate": self.gate_name,
            "loads": len(self.loads),
            "constants": len(self.consts),
            **dict(c),
            "terms": len(self.terms),
        }


class _CaptureOps:
    """The symbolic field-like ops face (GpuSynthesizerFieldLike analogue)."""

    def __init__(self, program: GateProgram):
        self.p = program

    def _new(self) -> int:
        s = self.p.num_slots
        self.p.num_slots += 1
        return s

    def zero(self):
        return self.constant(0)

    def one(self):
        return self.constant(1)

    def constant(self, v: int):
        s = self._new()
        self.p.consts.append((s, int(v) % gl.P))
        return Sym(s)

    def _bin(self, op, a: Sym, b: Sym):
        s = self._new()
        self.p.ops.append((op, s, a.idx, b.idx))
        return Sym(s)

    def add(self, a, b):
        return self._bin("add", a, b)

    def sub(self, a, b):
        return self._bin("sub", a, b)

    def mul(self, a, b):
        return self._bin("mul", a, b)

    def neg(self, a):
        s = self._new()
        self.p.ops.append(("neg", s, a.idx, a.idx))
        return Sym(s)

    def double(self, a):
        s = self._new()
        self.p.ops.append(("double", s, a.idx, a.idx))
        return Sym(s)


def capture_gate_program(gate, constants=()) -> GateProgram:
    """Run the gate's evaluator once over symbolic values, recording its
    constraint program (reference GPUDataCapture::from_evaluator,
    gpu_synthesizer/mod.rs:354)."""
    p = GateProgram(gate_name=gate.name)
    ops = _CaptureOps(p)

    def load(kind):
        def get(i):
            s = ops._new()
            p.loads.append((s, kind, i))
            return Sym(s)

        return get

    # memoize loads so repeated row.v(i) maps to one slot
    cache: dict = {}

    def memo(kind):
        raw = load(kind)

        def get(i):
            key = (kind, i)
            if key not in cache:
                cache[key] = raw(i)
            return cache[key]

        return get

    row = RowView(memo("v"), memo("w"), memo("c"))
    dst = TermsCollector()
    gate.evaluate(ops, row, dst)
    p.terms = [t.idx for t in dst.terms]
    return p


def capture_all(gates, constants_by_gate=None) -> dict:
    """Programs for a whole gate set (reference GatesSetForGPU,
    gpu_synthesizer/mod.rs:446)."""
    return {g.name: capture_gate_program(g) for g in gates}


# ---------------------------------------------------------------------------
# Scanned playback: O(1)-size compiled graphs for huge gate programs
# ---------------------------------------------------------------------------
# The prover's gate sweep normally traces gate.evaluate() directly, so the
# compiled graph grows with the evaluator's op count — for permutation-sized
# gates (the recursion circuit's flattened Poseidon2: thousands of field
# ops) XLA optimization time explodes super-linearly (the round-2 recursive
# prove never finished compiling). `pack_for_scan` register-allocates the
# SSA program (linear-scan liveness, so the live set stays near the gate's
# state width instead of one slot per op) and `scan_evaluate` replays it
# under ONE jax.lax.scan whose body is a single add/sub/mul switch — the
# graph size is constant in the program length. Bit-identical to direct
# tracing: same ops, same order, exact integer arithmetic.

from dataclasses import dataclass as _dataclass


@_dataclass
class PackedGateProgram:
    gate_name: str
    num_regs: int
    # ops: (T, 4) int32 [opcode(0=add,1=sub,2=mul), dst, a, b]
    ops_arr: object
    v_idx: tuple
    v_regs: tuple
    w_idx: tuple
    w_regs: tuple
    c_idx: tuple
    c_regs: tuple
    const_vals: tuple  # python ints
    const_regs: tuple
    term_regs: tuple
    num_ops: int


def pack_for_scan(prog: GateProgram) -> PackedGateProgram:
    """Lower a GateProgram to the register form scan_evaluate replays."""
    # prelower neg/double onto {add, sub, mul}; neg needs a zero constant
    consts = list(prog.consts)
    ops = []
    zero_slot = None
    for op, dst, a, b in prog.ops:
        if op == "neg":
            if zero_slot is None:
                zero_slot = prog.num_slots
                consts.append((zero_slot, 0))
            ops.append(("sub", dst, zero_slot, a))
        elif op == "double":
            ops.append(("add", dst, a, a))
        else:
            ops.append((op, dst, a, b))
    num_slots = prog.num_slots + (1 if zero_slot is not None else 0)

    # liveness: last position (op index) each slot is read; terms live forever
    last_use = [-1] * num_slots
    for t, (_op, _dst, a, b) in enumerate(ops):
        last_use[a] = t
        last_use[b] = t
    INF = len(ops) + 1
    for s in prog.terms:
        last_use[s] = INF

    # linear-scan allocation. Initial definitions (loads/consts) take regs
    # up front; an op's dst may reuse a reg freed at THIS op (operands are
    # read before the write in the scan body).
    reg_of = {}
    free: list = []
    next_reg = 0

    def alloc(slot):
        nonlocal next_reg
        if free:
            r = free.pop()
        else:
            r = next_reg
            next_reg += 1
        reg_of[slot] = r
        return r

    initial_defs = [s for (s, _k, _i) in prog.loads] + [
        s for (s, _v) in consts
    ]
    for s in initial_defs:
        alloc(s)
    # free initial defs never read at all (dead loads)
    for s in list(initial_defs):
        if last_use[s] < 0:
            free.append(reg_of[s])
    packed_ops = []
    for t, (op, dst, a, b) in enumerate(ops):
        ra, rb = reg_of[a], reg_of[b]
        # free operands whose last read is this op (dst may take the reg)
        for s in {a, b}:
            if last_use[s] == t:
                free.append(reg_of[s])
        rd = alloc(dst)
        if last_use[dst] < 0:  # dead op (term-less side effect): keep reg
            last_use[dst] = INF
        packed_ops.append(
            ({"add": 0, "sub": 1, "mul": 2}[op], rd, ra, rb)
        )

    import numpy as _np

    v_loads = [(i, reg_of[s]) for (s, k, i) in prog.loads if k == "v"]
    w_loads = [(i, reg_of[s]) for (s, k, i) in prog.loads if k == "w"]
    c_loads = [(i, reg_of[s]) for (s, k, i) in prog.loads if k == "c"]
    return PackedGateProgram(
        gate_name=prog.gate_name,
        num_regs=next_reg,
        ops_arr=_np.array(packed_ops, dtype=_np.int32).reshape(-1, 4),
        v_idx=tuple(i for i, _r in v_loads),
        v_regs=tuple(r for _i, r in v_loads),
        w_idx=tuple(i for i, _r in w_loads),
        w_regs=tuple(r for _i, r in w_loads),
        c_idx=tuple(i for i, _r in c_loads),
        c_regs=tuple(r for _i, r in c_loads),
        const_vals=tuple(v for (_s, v) in consts),
        const_regs=tuple(reg_of[s] for (s, _v) in consts),
        term_regs=tuple(reg_of[s] for s in prog.terms),
        num_ops=len(packed_ops),
    )


def scan_evaluate(packed: PackedGateProgram, row: RowView):
    """Replay a packed program over (n,)-array row values with lax.scan.

    Returns the term arrays, equal to gate.evaluate(ArrayOps, ...)."""
    import jax
    import jax.numpy as jnp

    from ..field import goldilocks as gf

    sample = None
    loads = []
    for idx, reg, getter in (
        [(i, r, row.v) for i, r in zip(packed.v_idx, packed.v_regs)]
        + [(i, r, row.w) for i, r in zip(packed.w_idx, packed.w_regs)]
        + [(i, r, row.c) for i, r in zip(packed.c_idx, packed.c_regs)]
    ):
        val = getter(idx)
        sample = val
        loads.append((reg, val))
    assert sample is not None, packed.gate_name
    n = sample.shape[-1]
    regs = jnp.zeros((packed.num_regs, n), jnp.uint64)
    if loads:
        regs = regs.at[jnp.asarray([r for r, _v in loads])].set(
            jnp.stack([jnp.broadcast_to(v, (n,)) for _r, v in loads])
        )
    if packed.const_vals:
        cvals = jnp.asarray(
            _np_array_u64(packed.const_vals)
        )
        regs = regs.at[jnp.asarray(packed.const_regs)].set(
            jnp.broadcast_to(cvals[:, None], (len(packed.const_vals), n))
        )

    ops_dev = jnp.asarray(packed.ops_arr)

    def step(regs, op):
        a = regs[op[2]]
        b = regs[op[3]]
        res = jax.lax.switch(
            op[0],
            (
                lambda x, y: gf.add(x, y),
                lambda x, y: gf.sub(x, y),
                lambda x, y: gf.mul(x, y),
            ),
            a,
            b,
        )
        regs = jax.lax.dynamic_update_index_in_dim(regs, res, op[1], 0)
        return regs, None

    regs, _ = jax.lax.scan(step, regs, ops_dev)
    return [regs[r] for r in packed.term_regs]


def _np_array_u64(vals):
    import numpy as _np

    return _np.array([int(v) % gl.P for v in vals], dtype=_np.uint64)


_PROGRAM_CACHE: dict = {}
_PACKED_CACHE: dict = {}


def program_for(gate) -> GateProgram:
    """`capture_gate_program(gate)`, captured once a gate."""
    if gate.name not in _PROGRAM_CACHE:
        _PROGRAM_CACHE[gate.name] = capture_gate_program(gate)
    return _PROGRAM_CACHE[gate.name]


def packed_program_for(gate, threshold: int | None = None):
    """The packed program for `gate` when its op count exceeds the scan
    threshold (BOOJUM_TPU_SCAN_GATE_THRESHOLD, default 256); None for small
    gates, which stay on the direct-trace path."""
    import os

    if threshold is None:
        threshold = int(
            os.environ.get("BOOJUM_TPU_SCAN_GATE_THRESHOLD", "256")
        )
    key = (gate.name, threshold)
    if key not in _PACKED_CACHE:
        prog = program_for(gate)
        _PACKED_CACHE[key] = (
            pack_for_scan(prog) if len(prog.ops) > threshold else None
        )
    return _PACKED_CACHE[key]
