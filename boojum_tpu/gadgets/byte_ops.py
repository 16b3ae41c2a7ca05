"""Shared byte-level circuit ops for the hash gadgets.

Counterpart of the helpers in
`/root/reference/src/gadgets/blake2s/mixing_function.rs:211` (`xor_many`,
`split_byte_using_table`, `merge_byte_using_table`) and
`/root/reference/src/gadgets/keccak256/round_function.rs` (`rotate_word`):
words are little-endian lists of byte variables; xor/and are 8-bit-table
lookups, rotations split bytes via per-split-point lookup tables and remerge
neighbouring halves with one FMA gate per output byte.
"""

from __future__ import annotations

from ..cs.gates.simple import FmaGate
from ..cs.lookup_table import and8_table, xor8_table
from .tables import byte_split_table


def ensure_table(cs, name: str, builder):
    return cs.ensure_table(name, builder)


def ensure_xor8(cs):
    return ensure_table(cs, "xor8", xor8_table)


def ensure_and8(cs):
    return ensure_table(cs, "and8", and8_table)


def ensure_byte_split(cs, split_at: int):
    return ensure_table(
        cs, f"byte_split_at{split_at}", lambda: byte_split_table(split_at)
    )


def xor_many(cs, a_bytes, b_bytes):
    xor_id = cs.get_table_id("xor8")
    return [
        cs.perform_lookup(xor_id, [a, b])[0] for a, b in zip(a_bytes, b_bytes)
    ]


def and_many(cs, a_bytes, b_bytes):
    and_id = cs.get_table_id("and8")
    return [
        cs.perform_lookup(and_id, [a, b])[0] for a, b in zip(a_bytes, b_bytes)
    ]


def range_check_byte(cs, v):
    """Force v in [0,256) via xor8 table membership (reference
    range_check_u8_pair, blake2s/mixing_function.rs)."""
    xor_id = cs.get_table_id("xor8")
    cs.perform_lookup(xor_id, [v, cs.zero_var()])


def range_check_byte_pairs(cs, byte_vars):
    """Force every variable into [0,256), two a lookup: (a, b) is a key of
    xor8 only if both are bytes (an odd one out pairs with zero)."""
    xor_id = cs.get_table_id("xor8")
    zero = cs.zero_var()
    for i in range(0, len(byte_vars), 2):
        pair = list(byte_vars[i : i + 2])
        cs.perform_lookup(xor_id, pair + [zero] * (2 - len(pair)))


def rotate_bytes_left(cs, word, r: int):
    """Rotate a little-endian byte-variable word left by r bits. The
    byte-aligned part is a free relabeling; the residual shift `rem` splits
    each byte at `8 - rem` via lookup and remerges neighbours with one FMA:
    out[j] = low[(j-k) % nb]·2^rem + high[(j-k-1) % nb]."""
    nb = len(word)
    k, rem = divmod(r % (8 * nb), 8)
    if rem == 0:
        return [word[(j - k) % nb] for j in range(nb)]
    split_id = cs.get_table_id(f"byte_split_at{8 - rem}")
    lows, highs = [], []
    for b in word:
        lo, hi = cs.perform_lookup(split_id, [b])
        lows.append(lo)
        highs.append(hi)
    one = cs.one_var()
    return [
        FmaGate.fma(cs, one, lows[(j - k) % nb], highs[(j - k - 1) % nb],
                    1 << rem, 1)
        for j in range(nb)
    ]


def rotate_bytes_right(cs, word, r: int):
    return rotate_bytes_left(cs, word, 8 * len(word) - (r % (8 * len(word))))
