"""round_offcpu_ms (ms, program counter): the milliseconds a round in which
the training thread neither computed nor sat in a wait that has a name: the
account's ``offcpu_ns``, which is the period less the named waits
(``waited_ns``: ``grow.wait_device``, ``grow.to_host``, the ``eval.*``
spans) less the thread's CPU time outside them (``cpu_ns`` of the loop's
top-level spans less ``waited_cpu_ns``, the waits' own ``cpu_ns``), over the
periods of the window's untraced rounds but the last (booster loop).  The
remainder as it comes out: the thread's clock may tick at 10 ms, so a window
whose true remainder is nought can read a few ms to either side of it, and a
reading below nought is logged.  A program without the account or the clocks
gives nothing."""
from benchmarks.metrics.gc_pause_ms import window_accounts


def read(ctx):
    accounts = window_accounts(ctx)
    if accounts is None or any(a.get("offcpu_ns") is None for a in accounts):
        return None
    n = len(accounts)
    period, waited, cpu, waited_cpu, off = (
        sum(a.get(k, 0) for a in accounts) / n * 1e-6
        for k in ("period_ns", "waited_ns", "cpu_ns", "waited_cpu_ns",
                  "offcpu_ns"))
    ctx["log"](f"round_offcpu_ms: a period of {period:.3f} ms, {waited:.3f} "
               f"in named waits, {cpu:.3f} on the CPU ({waited_cpu:.3f} of "
               f"it inside the waits); involuntary context switches "
               f"{sum(a['ctx_invol'] or 0 for a in accounts)}, major faults "
               f"{sum(a['majflt'] or 0 for a in accounts)}")
    if off < 0:
        ctx["log"](f"round_offcpu_ms: {off:.3f} is below nought: the CPU "
                   f"clock's tick, or CPU time counted twice")
    return off
