"""Share of the window the loop thread spent on its own host work: the
phases ``train.step`` (dispatch), ``train.transform``, ``train.bookkeep`` and
``train.log``, neither waiting for input (``input_wait_pct``) nor for the
device (``device_wait_pct``). With those two and the epoch turnovers
(``train.epoch_end`` + ``train.epoch_start``) it makes 100: the loop thread's
phases tile its time (``obs/spans.py``, ``SpanTracer.phase``)."""

from reduce import spans

OWN = ("train.step", "train.transform", "train.bookkeep", "train.log")


def read(ctx):
    if not any(s["name"] == "train.bookkeep" for s in ctx["spans"]):
        return None  # a program whose loop thread is not tiled by phases
    busy = sum(sum(spans.inside(ctx["spans"], name, ctx["window_ns"]))
               for name in OWN)
    return 100.0 * busy / (ctx["window_s"] * 1e9)
