"""Share of the window's steps whose dispatch began with nothing in flight
although the loop had not emptied the queue itself (no ``train.drain``, no
epoch's start and no sampled await directly before): the chips had run dry
because the host was late. The program's own counters
``train_dispatch_starved_total`` over ``train_steps_dispatched_total``
(``trainer._StepsInFlight``: the steps' loss arrays asked ``is_ready()``,
no wait), window difference. 0 in a device-bound run."""


def read(ctx):
    dispatched = ctx["counters"].get("train_steps_dispatched_total")
    if not dispatched:
        return None  # a program without the counters, or no step
    return 100.0 * ctx["counters"].get("train_dispatch_starved_total",
                                       0) / dispatched
