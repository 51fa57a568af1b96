"""The selective scan's share of the chip's published HBM bandwidth: the bytes
the scan's core REQUIRES, forward and backward (``scan_bytes``), for every
token trained in the traced window, per second of that window, over chips x
peak. The same kind of share as ``ssm.scan_hbm_pct`` is for the Mamba-2 scan:
what the operator needs against everything the window took, the same
whatever implements it (a kernel, a pass over positions), not a kernel's own
roofline (``lib/trace.py`` keeps the ten largest ops, so a reader cannot sum
the operator's device time yet; ``tools/anatomy.py`` can, by the
``selective_scan`` scope). The reference's FLOP count leaves the core out of
``round.mfu_pct`` (it holds no ``dot_general``), so this is where the core
shows. ``None`` where no flush span carries the Mamba-1 layers' constants (a
model without such layers, or a program from before they existed)."""


def scan_bytes(inner: int, state: int, itemsize: int = 2) -> float:
    """One token through one layer's scan core, forward and backward:
    forward reads ``u`` and the step before its softplus (``inner`` each) and
    ``B`` and ``C`` (``state`` each) in the compute dtype (bfloat16) and
    writes ``y`` (``inner``); backward reads those and ``dy`` again and writes
    ``du``, the step's gradient, ``dB`` and ``dC``. ``A``, ``D`` and the
    step's bias (numbers a channel and layer) and their gradients are no
    token's."""
    operands = (2 * inner + 2 * state) * itemsize
    result = inner * itemsize
    return (operands + result) + (operands + result + operands)


def read(run):
    trace = run["trace"]
    flushes = [a for n, _, _, a in run["program_spans"] if n == "flush" and "sscan_layers" in a]
    if trace is None or not flushes or not run["units"]:
        return None
    a = flushes[0]
    required = run["units"] * a["sscan_layers"] * scan_bytes(a["sscan_inner"], a["sscan_state"])
    return 100.0 * required / trace["window_s"] / (run["chips"] * run["peaks"]["hbm_bytes_per_s"])
