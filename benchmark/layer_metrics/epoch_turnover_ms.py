"""Median epoch turnover inside the window: ``train.epoch_end`` (loss fetch,
epoch metrics, eval, checkpoint) + ``train.epoch_start`` (loader rebuild up to
the first ``next``) + the new epoch's first ``train.loader`` (the pipeline's
refill, ``epoch_step`` 0). The three are consecutive phases of the loop
thread, so a turnover runs from the first's start to the last's end."""

import statistics


def read(ctx):
    lo, hi = ctx["window_ns"]
    loop = sorted((s for s in ctx["spans"] if s["name"] in (
        "train.epoch_end", "train.epoch_start", "train.loader")),
        key=lambda s: s["start_ns"])
    turnovers = []
    for end, start, first in zip(loop, loop[1:], loop[2:]):
        if (end["name"], start["name"], first["name"]) == (
                "train.epoch_end", "train.epoch_start", "train.loader") \
                and end["start_ns"] >= lo and first["end_ns"] <= hi:
            turnovers.append(first["end_ns"] - end["start_ns"])
    return statistics.median(turnovers) / 1e6 if turnovers else None
