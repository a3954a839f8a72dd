"""Operations and bytes of a FastGRNN cell's window, from the configuration's
shapes and the window's stream-steps.

The same count per stream-step as the program's
``Q15StreamStep.work_per_stream_step``, kept here so the yardstick does not
move with the program.  A stream-step is one sample of one stream advanced
through the cell (paper Eq. 1-3):

* FLOPs: 2 per multiply-add of the matvecs (low rank: W2^T x, W1 (.),
  U2^T h, U1 (.); full rank: W x, U h) plus 10 per hidden unit for the two
  bias adds, the two table reads' index arithmetic and the gate combine;
* HBM bytes: x in (d float32) and h in and out (2 H float32); weights and
  tables stay in fast memory for the whole dispatch.

Counted per advanced stream-step (the window's ``stream_steps`` counter),
never from padded lanes or grid rows.
"""
from __future__ import annotations


def per_stream_step(model: dict) -> dict:
    d, H = model["input_dim"], model["hidden_dim"]
    rw, ru = model.get("rank_w"), model.get("rank_u")
    if rw:
        mm = 2 * (d * rw + H * rw + H * ru + H * ru)
    else:
        mm = 2 * H * (d + H)
    return {"flops": mm + 10 * H, "hbm_bytes": 4 * (d + 2 * H)}


def count(model: dict, counters: dict) -> dict:
    """{"flops", "hbm_bytes"} of the window's stream-steps."""
    n = counters["stream_steps"]
    return {k: n * v for k, v in per_stream_step(model).items()}
