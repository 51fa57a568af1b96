"""The grouped-query attention core's share of the chip's published bf16
peak, in a model whose attention layers are of more than one shape: the
FLOPs its score and value products REQUIRE over the (query, key) pairs the
mask leaves live, forward and backward (``core_flops``), summed over the
kinds of layer, for every document trained in the traced window, per second
of that window, over chips x peak. A share of the peak over the WHOLE
window, as ``attention.core_peak_pct`` is for latent attention: what the
core needs, whatever implements it, against everything the window took.
The numbers come from the ``flush`` span's per-kind constants
(``attn_length``, ``attn_window``, ``attn_<kind>_layers``, ``_heads``,
``_head_dim``, kinds ``full`` and ``sliding``). ``None`` where no flush span
carries them (every attention layer of one shape, or a program from before
they existed)."""

KINDS = ("full", "sliding")


def live_pairs(length: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask leaves live over ``length``
    positions, ``i - j < window`` too where a window is given: query i sees
    ``min(i + 1, window)`` keys."""
    if not window or window >= length:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def core_flops(length: int, window: int, heads: int, head_dim: int) -> float:
    """One layer's core on one document, forward and backward: per query
    head the scores (head_dim deep) and the values (head_dim wide) over the
    live pairs at 2 FLOPs a multiply-add, once forward and 2.5 times
    backward (five products of the forward's two: the scores again, dP, dV,
    dQ and dK)."""
    return 3.5 * 2 * live_pairs(length, window) * heads * (head_dim + head_dim)


def read(run):
    trace = run["trace"]
    flushes = [a for n, _, _, a in run["program_spans"]
               if n == "flush" and any(f"attn_{k}_heads" in a for k in KINDS)]
    if trace is None or not flushes:
        return None
    a = flushes[0]
    T = a["attn_length"]
    per_document = sum(
        a[f"attn_{k}_layers"] * core_flops(T, a["attn_window"] if k == "sliding" else 0,
                                           a[f"attn_{k}_heads"], a[f"attn_{k}_head_dim"])
        for k in KINDS if f"attn_{k}_heads" in a)
    flops = run["units"] / T * per_document
    return 100.0 * flops / trace["window_s"] / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
