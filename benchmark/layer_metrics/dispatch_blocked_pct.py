"""Share of the window the loop thread waited for the device inside a
dispatch. The runtime holds only so many programs in flight, and a dispatch
into a full queue returns when a slot is free; the program cannot see the
limit, but since PR 35 its ``train.step`` phase says how many steps were in
flight when the dispatch began (``in_flight``, by ``is_ready()``, no wait).
For every dispatch of the loop thread that starts in the window (the
``train.step`` phase, ``train.transform`` less ``loop.transform_await``,
``loop.rng_split``, ``loop.loss_sum``, ``loop.stats_add``) in a step that
began with two or more steps in flight: its time beyond that name's unblocked
cost, the median of the same-named spans of steps that began with at most
one in flight. The sampled ``loop.transform_await`` is a wait for the device
inside ``train.transform`` and counts whole. With ``device_wait_pct`` the
whole of the loop's wait for the device (``reduce/loop_calls.py``)."""

from reduce import loop_calls


def read(ctx):
    shares = loop_calls.shares_of_run(ctx)
    return shares[0] if shares else None
