"""The least a device call must move, from its shapes, and the chip's
peaks. Kept with the benchmark so that no later PR can count differently."""

from __future__ import annotations

import json
import os

import numpy as np

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {_PEAKS}: "
                       f"add it with its source, do not guess")
    return table[device_kind]


def step_bytes(batch_rows: int, acc_kinds, acc_dtypes, slot_index_bytes: int = 4) -> int:
    """Bytes one scatter step of the slot aggregate has to move for a batch
    of ``batch_rows`` (padded) rows: the slot index of every row comes in;
    per accumulator lane the touched slots are read and written back, and a
    lane other than ``count`` also reads its value column. The state is
    donated, so untouched slots do not move. The step does no arithmetic
    worth counting (one add or compare per row and lane): it is bound by
    bytes, and its roofline is bytes over the HBM peak."""
    total = batch_rows * slot_index_bytes
    for kind, dtype in zip(acc_kinds, acc_dtypes):
        item = np.dtype(dtype).itemsize
        total += batch_rows * item * (2 if kind == "count" else 3)
    return total
