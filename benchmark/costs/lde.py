"""Compulsory HBM bytes of the low-degree extensions of one prove.

An LDE of B columns reads each input column once (n field elements) and
writes each output column once (n * L field elements). A Goldilocks element
is 8 bytes however it is laid out (one u64 or a lo/hi pair of u32 planes).
Twiddle tables, stage-by-stage re-reads and layout changes are an
implementation's traffic, not the algorithm's least, and are not counted:
a share computed from these bytes at the published bandwidth cannot pass
100 % for a kernel that reads and writes each array once.
"""

from __future__ import annotations

from .shapes import prove_commits

FIELD_BYTES = 8


def lde_bytes(columns: int, n: int, lde_factor: int) -> int:
    return FIELD_BYTES * int(columns) * int(n) * (1 + int(lde_factor))


def cost(shapes: dict) -> dict:
    """Per prove: the three commits' LDEs."""
    total = sum(
        lde_bytes(b, shapes["n"], shapes["L"]) for b in prove_commits(shapes)
    )
    return {"bytes": total, "ops": 0, "bound": "memory"}
