"""The least the sharded aggregate's step must move, from the rows it
carried and its accumulator lanes. Beside roofline.py, which no PR edits,
and kept with the benchmark for the same reason: so that no later PR can
count differently."""

from __future__ import annotations

KEY_BYTES = 8    # the int64 key hash
BIN_BYTES = 4    # the int32 bin
FLAG_BYTES = 1   # a row's ``valid``, a slot's occupancy


def step_bytes(rows: int, lane_bytes: int) -> int:
    """Bytes one step of the sharded aggregate (program ``jit_local_step``)
    has to move, over all its shards, for ``rows`` valid rows whose
    accumulator lanes take ``lane_bytes`` a row (both as the step's
    ``agg.dispatch`` span carries them).

    Per valid row its record (key, bin, valid, one value a lane) is read
    once where the host put it, written once into a send buffer and read
    once from a receive buffer: ``3 * (13 + lane_bytes)``. Per row one slot
    of the owner's table is then probed: its key, bin and occupancy read and
    compared (13 bytes), and each lane read and written back
    (``2 * lane_bytes``). ``rows * (52 + 5 * lane_bytes)``.

    A function of the work and not of any padded shape (the per-shard
    batch, the exchange lane, the receive buffer): a step that carries the
    same rows in fewer padded slots, or a re-based table, is read on the
    same bytes. Bytes-bound, by the convention of ``roofline.step_bytes``:
    the peak is HBM bandwidth, of all the cell's chips; the exchange's share
    of the interconnect's peak is left out (``peaks.json`` has no figure for
    it). Two sorts do log n times their least bytes, on buffers that are
    mostly padding, so the share is very small."""
    record = KEY_BYTES + BIN_BYTES + FLAG_BYTES + lane_bytes
    probe = KEY_BYTES + BIN_BYTES + FLAG_BYTES + 2 * lane_bytes
    return rows * (3 * record + probe)


def mesh_steps(run: dict) -> list:
    """The ``agg.dispatch`` spans of the measured window that a sharded
    aggregate recorded (those that carry ``room``); none on one chip, or
    from a program whose mesh path has no span."""
    from arroyo_tpu.obs import trace

    if not hasattr(trace, "spans"):
        return []
    w = run["window"]
    return [s for s in trace.spans("agg.dispatch", int(w["opened"] * 1e9),
                                   int(w["closed"] * 1e9))
            if s.args and s.args.get("room")]
