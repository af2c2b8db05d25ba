"""Does the chip's host count the CPU time of short bursts that follow a
sleep (PR 37)? ``time.thread_time()`` ticks in steps of 10 ms there, and
the state cells read over half of their host-only phases (0.1-0.6 ms
after sleeps of 20-80 ms) as off the CPU. A thread sleeps ``sleep_ms``
and then spins ``spin_ms``, ``n`` times: its CPU time should read the
spins' sum. Once alone (the process idles while it sleeps), once beside
a thread that never stops spinning (the process never idles)."""
import json
import threading
import time


def spin(seconds):
    t = time.perf_counter()
    while time.perf_counter() - t < seconds:
        pass


def bursts(sleep_ms, spin_ms, n, out):
    c0, t0 = time.thread_time(), time.perf_counter()
    for _ in range(n):
        time.sleep(sleep_ms * 1e-3)
        spin(spin_ms * 1e-3)
    out.update(cpu_read_ms=1e3 * (time.thread_time() - c0),
               cpu_spun_ms=spin_ms * n,
               wall_ms=1e3 * (time.perf_counter() - t0))


def run(sleep_ms, spin_ms, n, busy):
    stop = threading.Event()
    other = threading.Thread(
        target=lambda: [spin(1e-3) for _ in iter(stop.is_set, True)])
    if busy:
        other.start()
    out = {"sleep_ms": sleep_ms, "spin_ms": spin_ms, "n": n,
           "beside_a_spinning_thread": busy}
    worker = threading.Thread(target=bursts,
                              args=(sleep_ms, spin_ms, n, out))
    worker.start()
    worker.join()
    stop.set()
    if busy:
        other.join()
    return out


if __name__ == "__main__":
    for busy in (False, True):
        for sleep_ms, spin_ms in ((20, 0.3), (20, 2.0), (2, 0.3)):
            print(json.dumps(run(sleep_ms, spin_ms, 150, busy)),
                  flush=True)
