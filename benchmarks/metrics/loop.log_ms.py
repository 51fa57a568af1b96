"""Host time the train loop spends on the rows it flushes, per round of the
window, measured inside the program: the ``flush`` span's self time, its
duration less the ``flush_wait`` (the wait for the device) and ``eval``
(host.eval_ms) spans beneath it. What is left is ``_log_round``, the logger
and the bookkeeping round them."""


def read(run):
    spans = run["program_spans"]
    flushes = sum(e - s for n, s, e, _ in spans if n == "flush")
    if not flushes:
        return None
    beneath = sum(e - s for n, s, e, a in spans
                  if n in ("flush_wait", "eval") and a.get("parent") == "flush")
    return (flushes - beneath) / 1e3 / run["rounds"]
