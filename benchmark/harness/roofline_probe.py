"""The least the windowed join's device probe must move, from its shapes.
Beside roofline.py, which no PR edits, and kept with the benchmark for the
same reason: so that no later PR can count differently."""

from __future__ import annotations

KEY_BYTES = 8    # an int64 key, either side
INDEX_BYTES = 4  # an int32 of ``order``, ``lo`` or ``hi``


def probe_bytes(l_cap: int, r_cap: int) -> int:
    """Bytes one probe (program ``jit_probe``: sort the build side, search
    every probe key in it) has to move for padded sides of ``l_cap`` probe
    and ``r_cap`` build rows: both key columns come in at 8 bytes a row;
    ``order`` goes out at 4 bytes a build row, ``lo`` and ``hi`` at 4 bytes
    a probe row each. ``16 * l_cap + 12 * r_cap``.

    Bytes-bound, by the convention of ``roofline.step_bytes``: a compare a
    key and step is no arithmetic worth counting, and the peak is HBM
    bandwidth. A sort moves its keys log(r_cap) times and a search gathers
    log(r_cap) times a probe row, so the program does far more than its
    least bytes and the share stays well under 100%."""
    return l_cap * (KEY_BYTES + 2 * INDEX_BYTES) + r_cap * (KEY_BYTES + INDEX_BYTES)
