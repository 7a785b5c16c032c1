"""The control: the float8 reference put in the program's place comes out
not correct under the limit, at the small size, where the program is."""
import pytest

from small import SMALL_LIMIT, run_small


@pytest.mark.parametrize("workload", ["qwen3-1.7b.chat", "qwen2.5-32b.chat"])
def test_float8_control_fails_the_limit_the_program_meets(workload):
    res = run_small(workload, 3, control=True)
    assert res["check"]["max_logit_gap"]["value"] <= SMALL_LIMIT
    assert res["control_max_logit_gap"] > SMALL_LIMIT
