"""The control of each kind of cell, at a size a test run can hold: the
plain reference put in the program's place and computed one precision
below the configuration's (float8 e4m3 for its bfloat16 multiplies) is
judged by the runner's own comparison and comes out not correct, where
the program comes out correct."""
import pytest

from bench.tests import tiny


@pytest.mark.parametrize("cell,number", [
    ("lm_qwen25_3b.chat", "max_logit_dev"),
    ("cnn_vgg16.b32", "max_rel_dev")])
def test_control_fails_the_limit(cell, number):
    r = tiny.run(cell, seconds=1.5, control=True)
    assert r["correct"], r["checks"]
    assert not r["control"]["correct"], r["control"]
    control = r["control"]["checks"][number]
    assert control["value"] > control["limit"], r["control"]
