"""How well the capture's host and device clocks agreed, and what the
join did about it (``readers/xplane_join.py``):

- ``one_while_pct``: the share of ``decode_step`` spans inside the
  device's window that hold exactly one device ``while`` on the
  capture's OWN clocks, before any shift: the proof that the clocks
  agree, where it reads near 100;
- ``shift_ms``: the size of the shift the join then put on the device's
  clock so that every ``while`` lies inside the span that dispatched it
  (0 where none was needed; its sign is in the run's log).

Where ``shift_ms`` is not 0 the join was forced: the split of the idle
time between ``sched_idle_launch_ms`` and ``sched_idle_wait_logits_ms``
is good to about that many milliseconds, their sum and the other three
phases are not touched. ``None`` without the spans."""

from benchmark.readers import xplane_join


def read(ctx: dict, what: str):
    found = xplane_join.join(ctx)
    if found is None:
        return None
    one, of = found["raw_one_while"]
    if not of:
        return None
    if what == "shift_ms":
        return 1e3 * abs(found["device_shift_s"])
    return 100.0 * one / of
