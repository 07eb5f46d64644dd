"""The correctness check fails each control and fault a cell can have,
driven through the rest of a run at a tiny size on the CPU."""

import pytest


@pytest.mark.parametrize("fault", ["prefix32", "count_plus_one",
                                   "shift_position", "drop_half"])
def test_serving_check_fails(run_cell, fault):
    rc, line, err = run_cell("dna-seed-open", fault=fault,
                             overrides={"traffic": {"pattern_len": [19, 64]}})
    assert rc == 0, err
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["checks"].values())


@pytest.mark.parametrize("fault", ["early_stop", "state_unchanged",
                                   "bwt_flip"])
def test_build_check_fails(run_cell, fault):
    rc, line, err = run_cell("proteins-build", fault=fault)
    assert rc == 0, err
    assert line["correct"] is False
    assert line["checks"]["bad_index_rows"]["value"] > 0


def test_sound_serving_run_passes_with_long_seeds(run_cell):
    rc, line, _ = run_cell("dna-seed-open",
                           overrides={"traffic": {"pattern_len": [19, 64]}})
    assert rc == 0 and line["correct"] is True
