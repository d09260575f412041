"""Whether a call hands the interpreter lock over, measured and not timed:
what a task's thread pays for on a host where a dozen threads want the lock
is each hand-over (PERF.md section 6, PRs 45 and 48)."""

import time


def lets_go_of_the_lock(call) -> bool:
    """Whether ``call`` hands the interpreter lock over: with the switch
    interval out of reach nobody is forced to, so a second thread that waits
    for the lock runs only if ``call`` lets go of it of its own accord."""
    import sys
    import threading

    import gc

    ran, gate = [], threading.Lock()
    gate.acquire()

    def waiter():
        gate.acquire()  # parked without the lock until the gate opens
        ran.append(True)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1000.0)
    gc.disable()  # a collection may run a finalizer that lets go of it
    try:
        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        gate.release()  # it wants the lock now, and we hold it
        spun, until = 0, time.perf_counter() + 0.1
        while time.perf_counter() < until:  # pure Python: never lets go
            spun += 1
        assert not ran
        call()
        return bool(ran)
    finally:
        gc.enable()
        sys.setswitchinterval(interval)
        t.join(10)
