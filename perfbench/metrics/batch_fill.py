"""Requests completed over the slots of the batches closed, in %, from the
runtime's own counters over the window and its drain: ``completed`` over
(``batches_full`` + ``batches_deadline`` + ``batches_flush``) times the
largest batch."""


def read(run):
    c = run.counters
    batches = sum(c.get(k, 0) for k in
                  ("batches_full", "batches_deadline", "batches_flush"))
    if not batches or not run.max_batch:
        return None
    return c.get("completed", 0) / (batches * run.max_batch) * 100
