#!/usr/bin/env python3
"""``control.py`` with one more way to break the timed path: the slot
table's growth ceiling held at the capacity the table starts with, which is
the program as it was before tables grew. A cell whose keys outgrow the
table then spills rows to the host store and has to come out ``correct:
false`` for ``rows_spilled``.

    python3 benchmark/tests/control_ceiling.py --break held_ceiling \
        --workload q7-minute-sat --seed 5 --seconds 20

``breaks.py`` is not edited: the break is added to its table here."""

import contextlib
import sys

import breaks
import control


@contextlib.contextmanager
def held_ceiling():
    from arroyo_tpu.ops.slot_agg import SlotAggregator

    ceiling = SlotAggregator._ceiling
    SlotAggregator._ceiling = lambda self: self.cap
    try:
        yield
    finally:
        SlotAggregator._ceiling = ceiling


breaks.BREAKS["held_ceiling"] = held_ceiling

if __name__ == "__main__":
    sys.exit(control.main())
