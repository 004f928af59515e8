"""Share of the window the loop thread spent working: ``loop_busy_pct``'s
four phases (``train.step``, ``train.transform``, ``train.bookkeep``,
``train.log``) less ``dispatch_blocked_pct``, the part of them in which it
waited for the device inside a dispatch. What the host really spends on a
step: with ``input_wait_pct``, ``device_wait_pct``, ``dispatch_blocked_pct``
and the turnover phases (``train.epoch_end``, ``train.epoch_start``) it makes
100, since the loop thread's phases tile its time."""

from reduce import loop_calls


def read(ctx):
    shares = loop_calls.shares_of_run(ctx)
    return shares[1] - shares[0] if shares else None
