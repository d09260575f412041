"""Ways to break the timed path underneath the harness, each a context
manager that patches the program for the length of a run and restores it.
``control.py`` runs a cell with one of them on the chip; ``test_control.py``
does the same at rehearsal size. Every one has to come out ``correct:
false``; ``none`` is the program unbroken and has to come out true."""

import contextlib


@contextlib.contextmanager
def none():
    yield


@contextlib.contextmanager
def lossy_ingest():
    """One batch in ten never reaches the device step: rows lost between
    source and aggregate state (exactly-once broken)."""
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    update, calls = SlotAggregator._update_chunk, [0]

    def lossy(self, key_u64, bins, vals):
        calls[0] += 1
        if calls[0] % 10:
            return update(self, key_u64, bins, vals)

    SlotAggregator._update_chunk = lossy
    try:
        yield
    finally:
        SlotAggregator._update_chunk = update


@contextlib.contextmanager
def doubled_ingest():
    """One batch in ten is aggregated twice (at-least-once, not exactly-once)."""
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    update, calls = SlotAggregator._update_chunk, [0]

    def doubled(self, key_u64, bins, vals):
        calls[0] += 1
        if calls[0] % 10 == 0:
            update(self, key_u64, bins, vals)
        return update(self, key_u64, bins, vals)

    SlotAggregator._update_chunk = doubled
    try:
        yield
    finally:
        SlotAggregator._update_chunk = update


@contextlib.contextmanager
def unchanged_state():
    """The step returns its state unchanged: nothing is ever aggregated."""
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    update = SlotAggregator._update_chunk
    SlotAggregator._update_chunk = lambda self, key_u64, bins, vals: None
    try:
        yield
    finally:
        SlotAggregator._update_chunk = update


@contextlib.contextmanager
def off_by_one():
    """An answer altered where it is produced: the first accumulator of
    every close comes back one too high."""
    from arroyo_tpu.ops.slot_agg import SlotExtractHandle

    result = SlotExtractHandle.result

    def altered(self):
        keys, bins, accs = result(self)
        if len(keys):
            accs = [accs[0] + 1] + list(accs[1:])
        return keys, bins, accs

    SlotExtractHandle.result = altered
    try:
        yield
    finally:
        SlotExtractHandle.result = result


@contextlib.contextmanager
def checkpoint_never_durable():
    """After the warm-up's first, checkpoints are triggered and
    acknowledged but no epoch is made durable: the delivery guarantee has
    nothing to stand on."""
    from arroyo_tpu.engine.engine import Engine

    finish = Engine._finish_ready_epochs

    def first_epoch_only(self):
        later = {e: self._checkpoints.pop(e) for e in list(self._checkpoints) if e > 1}
        try:
            finish(self)
        finally:
            self._checkpoints.update(later)

    Engine._finish_ready_epochs = first_epoch_only
    try:
        yield
    finally:
        Engine._finish_ready_epochs = finish


@contextlib.contextmanager
def shifted_seller():
    """A column shifted by one event: every event carries the seller drawn
    for the event before it, so an epoch's first auction has the person's
    (none) and its last auction's is lost. As many rows as before reach
    every aggregate; the one keyed on the seller counts the wrong ones."""
    import numpy as np

    from arroyo_tpu.connectors.nexmark import NexmarkSource

    generate = NexmarkSource._generate

    def shifted(self, numbers):
        batch = generate(self, numbers)
        if "auction.seller" not in batch:
            return batch
        return batch.with_column("auction.seller", np.roll(batch["auction.seller"], 1))

    NexmarkSource._generate = shifted
    try:
        yield
    finally:
        NexmarkSource._generate = generate


BREAKS = {f.__name__: f for f in (none, lossy_ingest, doubled_ingest, unchanged_state,
                                  off_by_one, checkpoint_never_durable, shifted_seller)}
