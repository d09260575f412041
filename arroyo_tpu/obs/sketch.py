"""Key-skew sketches: deterministic space-saving top-k over routing hashes.

Skew-adaptive shuffle (ROADMAP item 3, PanJoin arXiv:1811.05065) and the
spill backend both need to know WHICH keys are hot before they can act; the
per-operator emit/queue histograms only say that *something* is hot. This
module is the detection layer: a space-saving heavy-hitter summary fed at
the shuffle/key boundaries (ShuffleCollector key hashing, keyed window/join
inserts via the task run loop), cheap enough to leave on in production.

Design constraints, in order:

  deterministic   replay after checkpoint restore must rebuild the same
                  summary — no randomness anywhere. Batch sampling uses a
                  counter whose phase is seeded from the subtask index
                  (decorrelates subtasks) and is part of the checkpointed
                  state, so a restored run resumes the exact sampling
                  cadence the original would have had. At the default
                  ``sample_every=1`` every row is counted exactly once, so
                  the summary is row-deterministic no matter how the
                  coalescing layer re-draws batch boundaries; sampling >1
                  is cheaper but boundary-sensitive (time-based coalesce
                  flushes can shift WHICH batches land on the sampled
                  phase), so it trades exact replay equality for cost.
  cheap           what a batch costs is the interpreter lock, which a
                  task's thread shares with a dozen others. A numpy call
                  that lets go of it has to win it back: on the chip's
                  host some 0.5 ms a call, where the whole fold is 0.4 ms
                  of CPU (PERF.md section 6, PR 45: the summary kept in
                  sorted arrays and folded with ``searchsorted`` and
                  ``lexsort`` took a quarter of the CPU and 1.4 times the
                  wall). These let go of it: ``np.sort``, ``np.unique``,
                  ``argsort``, ``lexsort``, ``partition``,
                  ``searchsorted``, ``take``, ``bincount`` and ``arange``
                  at any size; a ufunc, a copy, a ``concatenate``,
                  ``flatnonzero`` and ``np.full`` over 500 elements
                  (tests/test_profile.py holds ``observe`` to none of
                  them). So per SAMPLED batch (1/``sample_every``): the
                  rows counted by key in 500-row pieces, a piece of one
                  key by one comparison and any other in a ``Counter``;
                  the keys the summary holds found by one set
                  intersection and added to in place; of the keys it
                  lacks the ``capacity`` largest picked by one
                  ``sorted``, so that the arrays the eviction runs over
                  hold twice the capacity at most, with the two order
                  statistics it needs from ``sorted`` again. Skipped
                  batches cost one integer increment.
  mergeable       rescale restore can hand one subtask several prior
                  subtasks' summaries; ``merge_state`` implements the
                  standard space-saving merge (absent keys are compensated
                  with the other summary's eviction threshold), so the
                  union never under-counts a heavy hitter.

Counts are over the 64-bit routing hash (``_key``), not the user key value:
that is what exists at every shuffle boundary, and it is enough to detect
and act on skew (split/replicate by hash). ``error`` per entry is the
standard space-saving overestimate bound — ``count - error`` is a
guaranteed lower bound on the key's true traffic.
"""

from __future__ import annotations

from collections import Counter
from typing import Optional

import numpy as np

# numpy keeps the interpreter lock around a loop over at most this many
# elements (NPY_BEGIN_THREADS_THRESHOLDED) and lets go of it around a longer
_PIECE = 500


def _rows_by_key(keys: np.ndarray) -> Counter:
    """How many of the batch's rows each key has, without a call that lets
    go of the lock: a piece of ``_PIECE`` rows at a time, a piece that is one
    key throughout by one comparison (the rows a windowed join takes and
    hands on are one key a window, tens of thousands of rows a batch), any
    other counted row by row in C."""
    batch: Counter = Counter()
    for lo in range(0, len(keys), _PIECE):
        piece = keys[lo:lo + _PIECE]
        first = piece[0]
        if (piece == first).all():
            batch[int(first)] += len(piece)
        else:
            batch.update(piece.tolist())
    return batch


class KeySketch:
    """Space-saving top-k summary of uint64 routing-hash traffic.

    The summary is three arrays of one length, at most ``capacity``: the key
    hashes (uint64, in no order), their estimated counts and their error
    bounds (int64; an error of 0 is a key that never re-entered), and a dict
    from key to its slot in them. The task's thread folds every keyed batch
    in, outside every hook (``task.account``'s ``sketch``)."""

    __slots__ = ("capacity", "sample_every", "_arrays", "_slot", "threshold",
                 "total", "_tick")

    def __init__(self, capacity: int = 64, sample_every: int = 1,
                 seed: int = 0):
        self.capacity = max(1, int(capacity))
        self.sample_every = max(1, int(sample_every))
        # (keys, counts, errors), replaced whole whenever a key enters or
        # leaves: a reader on another thread (the metrics export's topk)
        # takes the three in one load and never sees two lengths
        self._arrays = (np.empty(0, dtype=np.uint64),
                        np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64))
        self._slot: dict[int, int] = {}  # key -> its index in the arrays
        # max count ever evicted: an absent key may have accumulated up to
        # this much traffic before eviction, so re-entries start from here
        self.threshold = 0
        self.total = 0  # rows represented (sampled rows x sample_every)
        # deterministic sampling phase; the seed (subtask index) decorrelates
        # which batches different subtasks sample without randomness (LR103)
        self._tick = int(seed) % self.sample_every

    # ------------------------------------------------------------------ feed

    def observe(self, keys: np.ndarray) -> None:
        """Count one batch's routing keys (1/sample_every batches counted;
        the rest cost a single increment)."""
        self._tick += 1
        if self._tick % self.sample_every:
            return
        n = len(keys)
        if n == 0:
            return
        scale = self.sample_every
        self.total += n * scale
        batch = _rows_by_key(np.asarray(keys, dtype=np.uint64))
        if scale != 1:
            batch = {key: count * scale for key, count in batch.items()}
        slot = self._slot
        held = slot.keys() & batch.keys()
        if held:
            # distinct keys: no index repeats, so the in-place add is exact
            self._arrays[1][[slot[k] for k in held]] += [batch.pop(k) for k in held]
        if not batch:
            return
        # space-saving entry: a new key inherits the eviction threshold (as
        # it stood when the batch began) as both starting mass and error
        thr = self.threshold
        if len(batch) > self.capacity:
            # more new keys than the summary has room for: only the largest
            # by (count, key) can stay, and the largest of the others is the
            # least the threshold rises to. The arrays below then never
            # pass twice the capacity, whatever the batch brought
            ranked = sorted(zip(batch.values(), batch), reverse=True)
            self.threshold = thr + ranked[self.capacity][0]
            batch = {key: count for count, key in ranked[:self.capacity]}
        u = np.fromiter(batch, np.uint64, len(batch))
        c = np.fromiter(batch.values(), np.int64, len(batch))
        self._enter(u, c + thr, np.full(len(u), thr))

    def _enter(self, u: np.ndarray, c: np.ndarray, e: np.ndarray) -> None:
        """Take in keys the summary lacks, then, over capacity, throw out
        the smallest counts (ties: the smallest keys) and raise the
        threshold to the largest count thrown out."""
        keys, counts, errors = self._arrays
        keys = np.concatenate((keys, u))
        counts = np.concatenate((counts, c))
        errors = np.concatenate((errors, e))
        over = len(keys) - self.capacity
        if over > 0:
            cut = sorted(counts.tolist())[over - 1]  # the largest count to go
            if cut > self.threshold:
                self.threshold = cut
            out, tied = counts < cut, counts == cut
            # of the keys tied at the cut, as many go as are still over:
            # the smallest
            short = over - np.count_nonzero(out)
            tied_keys = keys[tied].tolist()
            if short < len(tied_keys):
                tied &= keys <= np.uint64(sorted(tied_keys)[short - 1])
            keep = ~(out | tied)
            keys, counts, errors = keys[keep], counts[keep], errors[keep]
        self._arrays = (keys, counts, errors)
        self._slot = dict(zip(keys.tolist(), range(len(keys))))

    # ----------------------------------------------------------------- views

    def topk(self, k: int = 8) -> list[dict]:
        """[{key, count, error, share}] by count desc (ties key asc);
        ``share`` is count/total traffic, ``count - error`` a guaranteed
        lower bound on the key's true rows."""
        keys, counts, errors = self._arrays
        order = np.lexsort((keys, -counts))[:k]
        total = self.total or 1
        return [
            {"key": key, "count": cnt, "error": err,
             "share": round(cnt / total, 4)}
            for key, cnt, err in zip(keys[order].tolist(), counts[order].tolist(),
                                     errors[order].tolist())
        ]

    # ------------------------------------------------------ checkpoint state

    def state(self) -> dict:
        """Plain-python snapshot for the checkpointed ``__sketch`` table:
        ``errors`` holds the non-zero bounds only."""
        keys, counts, errors = self._arrays
        bounded = errors != 0
        return {
            "counts": dict(zip(keys.tolist(), counts.tolist())),
            "errors": dict(zip(keys[bounded].tolist(), errors[bounded].tolist())),
            "threshold": self.threshold,
            "total": self.total,
            "tick": self._tick,
            "sample_every": self.sample_every,
        }

    def merge_state(self, state: Optional[dict]) -> None:
        """Fold a persisted summary in (restore; rescale may fold several).
        Space-saving merge: keys absent from one side are compensated with
        that side's threshold, so the union never under-counts."""
        if not state:
            return
        theirs = {int(k): int(v) for k, v in state.get("counts", {}).items()}
        their_errors = {int(k): int(v) for k, v in state.get("errors", {}).items()}
        other_thr = int(state.get("threshold", 0))
        keys, counts, errors = self._arrays
        merged_fresh = not len(keys) and not self.total
        slot = self._slot
        held = list(slot.keys() & theirs.keys())
        at = [slot[k] for k in held]
        if held:
            counts[at] += [theirs.pop(k) for k in held]
            errors[at] += [their_errors.get(k, 0) for k in held]
        if other_thr:
            # keys the other summary evicted may include any of ours: every
            # key absent from it gets its threshold as compensation too
            alone = np.ones(len(keys), dtype=bool)
            alone[at] = False
            counts[alone] += other_thr
            errors[alone] += other_thr
        u = np.fromiter(theirs, np.uint64, len(theirs))
        c = np.fromiter(theirs.values(), np.int64, len(theirs))
        e = np.array([their_errors.get(k, 0) for k in theirs], dtype=np.int64)
        entry = self.threshold
        self.threshold += other_thr
        self._enter(u, c + entry, e + entry)
        self.total += int(state.get("total", 0))
        if merged_fresh:
            # restoring our own prior state: resume the exact sampling phase
            self._tick = int(state.get("tick", self._tick))


def merge_topk(topks, total: int, k: int = 8) -> list[dict]:
    """Merge exported per-subtask top-k lists ([{key, count, error, share}],
    keys already hex-encoded by the metrics export) into one per-operator
    list. Counts for a key absent from some subtask's list are lower bounds
    (that subtask's below-top-k mass is not exported), which is the safe
    direction for skew detection: a key this merge calls hot IS hot.
    ``total`` is the summed per-subtask traffic, for the merged share."""
    counts: dict[str, int] = {}
    errors: dict[str, int] = {}
    for lst in topks:
        for e in lst or ():
            key = e["key"]
            counts[key] = counts.get(key, 0) + int(e["count"])
            err = int(e.get("error", 0))
            if err:
                errors[key] = errors.get(key, 0) + err
    # fixed-width hex sorts lexically == numerically: deterministic ties
    order = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
    t = total or 1
    return [{"key": key, "count": c, "error": errors.get(key, 0),
             "share": round(c / t, 4)}
            for key, c in order[:k]]
