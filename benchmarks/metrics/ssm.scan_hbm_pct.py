"""The state-space scan's share of the chip's published HBM bandwidth: the
bytes the scan's core REQUIRES, forward and backward (``scan_bytes``), for
every token trained in the traced window, per second of that window, over
chips x peak. The same kind of share as ``conv.gated_hbm_pct``: what the
operator needs against everything the window took, the same whatever
implements it (chunked products, a kernel, a pass over positions), not a
kernel's own roofline (``lib/trace.py`` keeps the ten largest ops, so a
reader cannot sum the operator's device time yet; ``tools/anatomy.py`` can,
by the ``ssd`` scope). The reference's FLOP count leaves the core out of
``round.mfu_pct`` (it is written without a ``dot_general``), so this is where
the core shows. ``None`` where no flush span carries the state-space layers'
constants (a model without such layers, or a program from before they
existed)."""


def scan_bytes(heads: int, head_dim: int, groups: int, state: int, itemsize: int = 2) -> float:
    """One token through one layer's scan core, forward and backward:
    forward reads ``x`` (heads x head_dim), ``B`` and ``C`` (groups x state
    each) in the compute dtype (bfloat16) and the step (one float32 a head)
    and writes ``y`` (heads x head_dim); backward reads those and ``dy``
    again and writes ``dx``, ``dB``, ``dC`` and the step's gradient. ``A`` and
    ``D`` (one number a head and layer) and their gradients are no token's."""
    operands = (heads * head_dim + 2 * groups * state) * itemsize + heads * 4
    result = heads * head_dim * itemsize
    return (operands + result) + (operands + result + operands)


def read(run):
    trace = run["trace"]
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and "ssm_layers" in a]
    if trace is None or not flushes or not run["units"]:
        return None
    a = flushes[0]
    required = run["units"] * a["ssm_layers"] * scan_bytes(
        a["ssm_heads"], a["ssm_head_dim"], a["ssm_groups"], a["ssm_state"])
    return 100.0 * required / trace["window_s"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])
