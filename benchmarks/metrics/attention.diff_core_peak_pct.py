"""The differential attention core's share of the chip's published bf16 peak:
the FLOPs its two maps' score products (over the keys' width) and value
products (over the values' width, twice the keys') REQUIRE over the (query,
key) pairs the mask leaves live, forward and backward (``core_flops``),
summed over the kinds of layer (window, full, cross), for every document
trained in the traced window, per second of that window, over chips x peak.
A share of the peak over the WHOLE window, in the form of
``attention.gqa_core_peak_pct``: what the core needs, whatever implements it
(the kernel pads the keys to the values' width, which this does not count),
against everything the window took. The numbers come from the ``flush``
span's constants (``diff_length``, ``diff_window``, ``diff_<kind>_layers``,
``diff_pairs``, ``diff_key_dim``, ``diff_value_dim``). ``None`` where no flush
span carries them (a model without differential attention, or a program from
before it existed)."""

KINDS = ("sliding", "full", "cross")


def live_pairs(length: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask leaves live over ``length``
    positions, ``i - j < window`` too where a window is given: query i sees
    ``min(i + 1, window)`` keys."""
    if not window or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def core_flops(length: int, window: int, pairs: int, key_dim: int, value_dim: int) -> float:
    """One layer's core on one document, forward and backward: two maps a
    pair of heads, each with its scores (``key_dim`` deep) and its values
    (``value_dim`` wide) over the live pairs at 2 FLOPs a multiply-add, once
    forward and 2.5 times backward (five products of the forward's two: the
    scores again, dP, dV, dQ and dK)."""
    return 3.5 * 2 * live_pairs(length, window) * 2 * pairs * (key_dim + value_dim)


def read(run):
    trace = run["trace"]
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and "diff_pairs" in a]
    if trace is None or not flushes:
        return None
    a = flushes[0]
    T = a["diff_length"]
    per_document = sum(
        a[f"diff_{k}_layers"] * core_flops(T, a["diff_window"] if k == "sliding" else 0,
                                           a["diff_pairs"], a["diff_key_dim"], a["diff_value_dim"])
        for k in KINDS)
    flops = run["units"] / T * per_document
    return 100.0 * flops / trace["window_s"] / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
