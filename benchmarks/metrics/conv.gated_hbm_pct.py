"""The gated short convolution's share of the chip's published HBM bandwidth:
the bytes the operator REQUIRES, forward and backward (``conv_bytes``), for
every token trained in the traced window, per second of that window, over
chips x peak. A share of the bandwidth peak over the WHOLE window, as
``moe.expert_peak_pct`` and ``attention.core_peak_pct`` are of the FLOP peak:
what the operator needs against everything the window took, the same
whatever implements it, not a kernel's own roofline (``lib/trace.py`` keeps
the ten largest ops, so a reader cannot sum the operator's device time yet;
``tools/anatomy.py`` can, by the ``short_conv`` scope). ``None`` where no
flush span carries the conv layers' constants (a model without such layers,
or a program from before they existed)."""


def conv_bytes(width: int, itemsize: int = 2) -> float:
    """One token through one conv layer's core, forward and backward, in the
    compute dtype (bfloat16): forward reads ``B``, ``C`` and ``X`` and writes
    the gated sum (4 x width numbers); backward reads that sum's gradient and
    ``B``, ``C``, ``X`` again and writes their three gradients (7 x width).
    The filter (width x taps numbers a layer) and its gradient are not a
    token's."""
    return (4 + 7) * width * itemsize


def read(run):
    trace = run["trace"]
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and "conv_layers" in a]
    if trace is None or not flushes or not run["units"]:
        return None
    a = flushes[0]
    required = run["units"] * a["conv_layers"] * conv_bytes(a["conv_width"])
    return 100.0 * required / trace["window_s"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])
