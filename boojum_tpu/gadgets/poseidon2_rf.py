"""Poseidon2 circuit round function + sponge gadget.

Counterpart of `/root/reference/src/gadgets/poseidon2/mod.rs` (circuit round
function delegating to the flattened gate) and the generic algebraic sponge
(`/root/reference/src/algebraic_props/sponge.rs`) instantiated over circuit
variables: rate 8 / capacity 4 / overwrite mode, bit-compatible with the
device sponge (`boojum_tpu.hashes.poseidon2`) and the host mirror
(`Poseidon2SpongeHost`) — the recursion circuit's transcript and tree hasher
hash exactly like the prover's.
"""

from __future__ import annotations

from ..cs.gates.poseidon2_flat import SW, Poseidon2FlattenedGate

RATE = 8
CAPACITY = 4


def circuit_permutation(cs, state_vars):
    """One width-12 permutation over circuit variables (one flattened-gate
    instance)."""
    return Poseidon2FlattenedGate.permutation(cs, state_vars)


class CircuitPoseidon2Sponge:
    """Overwrite-mode sponge over circuit variables (reference
    sponge.rs:172 generic sponge; absorb order matches Poseidon2SpongeHost)."""

    def __init__(self, cs):
        self.cs = cs
        zero = cs.zero_var()
        self.state = [zero] * SW
        self.buffer: list = []

    def absorb(self, variables):
        self.buffer.extend(variables)
        while len(self.buffer) >= RATE:
            chunk, self.buffer = self.buffer[:RATE], self.buffer[RATE:]
            self.state = circuit_permutation(
                self.cs, chunk + self.state[RATE:]
            )

    def finalize(self, n=CAPACITY):
        if self.buffer:
            zero = self.cs.zero_var()
            pad = [zero] * (RATE - len(self.buffer))
            self.state = circuit_permutation(
                self.cs, self.buffer + pad + self.state[RATE:]
            )
            self.buffer = []
        return self.state[:n]


def circuit_hash_leaf(cs, variables, n=CAPACITY):
    sp = CircuitPoseidon2Sponge(cs)
    sp.absorb(list(variables))
    return sp.finalize(n)


def circuit_hash_node(cs, left, right):
    sp = CircuitPoseidon2Sponge(cs)
    sp.absorb(list(left) + list(right))
    return sp.finalize(CAPACITY)


def circuit_merkle_root(cs, leaf_vars, n=CAPACITY):
    """Root of the binary Merkle tree over `leaf_vars` (a power-of-two
    number of variable lists): `circuit_hash_leaf` on each leaf, then node
    hashes (two digests, one permutation) layer by layer to one digest of
    `n` variables: what the recursion's tree hasher does in a verifier
    circuit (reference gadgets/recursion/recursive_tree_hasher.rs)."""
    layer = [circuit_hash_leaf(cs, leaf, n) for leaf in leaf_vars]
    assert layer and len(layer) & (len(layer) - 1) == 0, len(layer)
    while len(layer) > 1:
        layer = [
            circuit_hash_node(cs, layer[i], layer[i + 1])
            for i in range(0, len(layer), 2)
        ]
    return layer[0]
