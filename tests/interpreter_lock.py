"""Whether a call hands the interpreter lock over, measured and not timed:
what a task's thread pays for on a host where a dozen threads want the lock
is each hand-over (PERF.md section 6, PRs 45, 48 and 49)."""

import contextlib
import time


@contextlib.contextmanager
def _a_thread_that_wants_the_lock(run):
    """Inside, a second thread waits for the interpreter lock to call
    ``run`` and nobody is forced to give it up: with the switch interval out
    of reach that thread runs only if the code inside lets go of the lock of
    its own accord."""
    import gc
    import sys
    import threading

    gate = threading.Lock()
    gate.acquire()

    def waiter():
        gate.acquire()  # parked without the lock until the gate opens
        run()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1000.0)
    gc.disable()  # a collection may run a finalizer that lets go of it
    t = threading.Thread(target=waiter)
    try:
        t.start()
        time.sleep(0.05)
        gate.release()  # it wants the lock now, and we hold it
        spun, until = 0, time.perf_counter() + 0.1
        while time.perf_counter() < until:  # pure Python: never lets go
            spun += 1
        yield
    finally:
        gc.enable()
        sys.setswitchinterval(interval)
        t.join(10)


def lets_go_of_the_lock(call) -> bool:
    """Whether ``call`` hands the interpreter lock over."""
    ran = []
    with _a_thread_that_wants_the_lock(lambda: ran.append(True)):
        assert not ran
        call()
        return bool(ran)


def hand_overs(call, runs: int = 1, until: int = 0) -> int:
    """How many of the calls that ``call`` makes hand the interpreter lock
    over, as the second thread sees them: each time it gets the lock it
    notes where ``call`` stood (a callback before each of its instructions
    keeps that: the stack of code and offset, and which visit of that place
    it is), and waits until ``call`` is back in Python before it asks for
    the lock again. A call that lets go twice before it returns counts once,
    and one that takes the lock back before the waiter woke (tens of
    microseconds on a quiet machine, milliseconds on a busy one) is missed:
    so ``call`` is made ``runs`` times, taking the same path each time (or
    until ``until`` places have been seen), and the places seen in any run
    are counted. The count is never too high."""
    import sys
    import threading

    mon, tool = sys.monitoring, 3
    seen, stop, back = set(), [], threading.Event()
    main, here = threading.get_ident(), sys._getframe()
    at, visits = [None], {}

    def note():
        while not stop:
            seen.add(at[0])  # it holds the lock: ``call`` let go of it there
            back.clear()
            back.wait()      # gives it back, and sleeps until ``call`` has it

    def on_instruction(code, offset):
        if threading.get_ident() != main:
            return
        place, f = [], sys._getframe(1)
        while f is not None and f is not here:
            place.append((f.f_code, f.f_lasti))
            f = f.f_back
        place = tuple(place)
        visits[place] = visits.get(place, 0) + 1
        at[0] = (place, visits[place])
        back.set()

    with _a_thread_that_wants_the_lock(note):
        try:
            assert not seen
            mon.use_tool_id(tool, "hand-overs")
            mon.register_callback(tool, mon.events.INSTRUCTION, on_instruction)
            try:
                for _ in range(runs):
                    visits.clear()
                    mon.set_events(tool, mon.events.INSTRUCTION)
                    call()
                    mon.set_events(tool, 0)
                    if until and len(seen) >= until:
                        break
            finally:
                mon.set_events(tool, 0)
                mon.register_callback(tool, mon.events.INSTRUCTION, None)
                mon.free_tool_id(tool)
            return len(seen)
        finally:
            stop.append(True)
            back.set()
