"""The control: the reference in the program's place, computed in
bfloat16, must fail the comparison that a float32 run passes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tiny import cell  # noqa: E402

import control  # noqa: E402
from reference import judge  # noqa: E402


@pytest.mark.parametrize("workload", ["dpr-flat.steady", "dpr-flat.live"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_bf16_control_is_not_correct(workload, seed):
    _, config, traffic = cell(workload)
    config["corpus"].update(passages=5000, entity_passages=500)
    config["engine"]["dim"] = 4096
    numbers = control.control_numbers(workload, config, traffic, seed,
                                      seconds=30.0, sample=128)
    correct, checks = judge(numbers, config["limits"])
    assert correct is False
    assert checks["score_gap"]["value"] > 3 * checks["score_gap"]["limit"]
