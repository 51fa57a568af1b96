"""The control has to come out as not correct: the plain reference, computed
in the configuration's next lower precision and put in the program's place,
fails at least one of the cell's numbers under the cell's own limits. Kept
here at a size a test run can hold: the rehearsal size, except for
``femnist-cnn.c10``, whose looser limits the rehearsal-size control stays
under; it runs the cell's own cohort, batch and client sizes over a population
of 40 clients. The readings at the cells' own sizes are in PERF.md
(``tools/readings.py --control`` on the chip)."""

import copy
import pathlib

import pytest

from benchmarks import run
from benchmarks.lib import compare, fedavg_ref, feed as feed_mod, window

ROOT = pathlib.Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("cell_name", ["gpt2-124m.silo4", "femnist-cnn.c200", "femnist-cnn.c10"])
def test_control_is_not_correct(cell_name):
    bench = run.load_json(ROOT / "BENCHMARK.json")
    own_cohort = cell_name == "femnist-cnn.c10"
    _, cfg, cell, limits, ref = run.load_cell(bench, cell_name, rehearse=not own_cohort)
    if own_cohort:
        cfg = copy.deepcopy(cfg)
        cfg["population"].update(clients=40, test_samples=256)
        cfg["reference_client_block"] = 5
    followed = window.FOLLOWED
    block = int(cfg.get("reference_client_block", 32))
    feed = feed_mod.Feed(cfg, cell, 11)
    sound = fedavg_ref.follow(ref, cfg, cell, feed, 11, followed, client_block=block)
    ops = fedavg_ref.Ops(**cfg["precision"]["control_ops"])
    low = fedavg_ref.follow(ref, cfg, cell, feed, 11, followed, ops=ops, client_block=block)
    assert compare.decide(compare.numbers(sound, sound), limits, 0)[0] is True
    correct, compared = compare.decide(compare.numbers(low, sound), limits, 0)
    assert correct is False, compared
