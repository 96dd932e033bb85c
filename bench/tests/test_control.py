"""The control of the comparison: the reference computed in bfloat16, put
in the program's place, reads above the limit; the program reads zero.
Both configurations at their published widths, under small traffic (at the
registry's smoke widths bfloat16 rounding flips no activation)."""
import pytest

import support


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return support.make_bench(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["dvs_full", "cifar_full"])
def test_control_fails_where_the_program_passes(bench, cell):
    r = support.run(bench, cell, seed=5_000_000_017, seconds=2.0, control=True)
    limit = r["checks"]["logit_err"]["limit"]
    assert r["checks"]["logit_err"]["value"] <= limit
    assert r["control"]["logit_err"] > limit
