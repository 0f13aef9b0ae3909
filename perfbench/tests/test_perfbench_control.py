"""The control, at a size a test run holds: the reference one precision
step below the configuration's, in the program's place, has to come out
not correct at the limits the chip's readings set."""
import importlib
import json

import pytest

from perfbench.tests import tiny


def _limits(name):
    return json.loads((tiny.BENCH / "configs" / f"{name}.json").read_text())[
        "check_limits"]


@pytest.mark.parametrize("config,traffic,reading,limit", [
    ("scaledoc-paper-4096", "cold-compound", "control.score_gap", "score_gap"),
    ("scaledoc-paper-4096", "cold-compound", "fault.half_batch.train_loss_gap",
     "train_loss_gap"),
    ("scaledoc-paper-4096", "cold-compound", "fault.fewer_steps.train_change_gap",
     "train_change_gap"),
    ("scaledoc-paper-4096", "cold-compound", "fault.no_band_f1", "leaf_f1_min"),
    ("smollm-360m", "ingest-512", "control.row_rel_err", "row_rel_err"),
])
def test_control_fails_the_chip_limit(config, traffic, reading, limit, tmp_path):
    """The control, or a planted fault, reads past the limit that the
    chip's readings set, and the program's own run stays inside it."""
    cfg = tiny.config(config)
    cfg["check_limits"] = _limits(config)
    run = tiny.run(config, traffic, tmp_path, seconds=0.5, cfg=cfg)
    if config == "smollm-360m":
        run.config["torch_dtype"] = "bfloat16"
    runner = importlib.import_module(f"perfbench.runners.{run.traffic['runner']}")
    measured = runner.measure(run)
    program = {c.name: c for c in measured.check()}
    control = measured.control()
    check = program[limit]
    if check.kind == "max":
        assert control[reading] > check.limit
    else:
        assert control[reading] < check.limit
    assert check.ok, check
