"""Bytes a tiered step needs over the host link, in `qbench.work`'s manner:
the work of the algorithm, not of an implementation. A step needs each of
its cold rows on the chip once; the padding of the block that carries them,
the id map beside it and the runtime's staging copy count nothing."""

from __future__ import annotations


def h2d_bytes(cold_rows: float, row_bytes: int) -> float:
    """Bytes the host link must carry a step: every valid cold row once."""
    return float(cold_rows) * row_bytes
