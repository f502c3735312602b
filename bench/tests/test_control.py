"""The controls at a size a CPU test run holds, each put in the program's
place: the run has to report ``correct`` false.  The fp8 reference's
first tokens lie further below the reference's best than the program's
served tokens, and the simulator reference without tRCD disagrees with
the program."""
import pytest

import tiny


@pytest.mark.parametrize("mix,cfg", [("open_loop", "TINY"),
                                     ("offline", "TINY"),
                                     ("offline", "TINY_P99")])
@pytest.mark.parametrize("seed", [11, 2 ** 33 + 5, 977])
def test_fp8_control_in_the_programs_place_is_not_correct(seed, mix, cfg):
    mix = tiny.OPEN if mix == "open_loop" else tiny.OFFLINE
    cfg = getattr(tiny, cfg)
    (number, limit), = cfg["check"].items()
    prog = tiny.serve(cfg=cfg, seed=seed, mix=mix)
    ctl = tiny.serve(cfg=cfg, seed=seed, mix=mix, control=True)
    assert prog["correct"], prog["checks"]
    assert not ctl["correct"], ctl["checks"]
    gap = ctl["checks"][number]["value"]
    assert gap > limit
    assert gap >= 3 * prog["checks"][number]["value"]


@pytest.mark.parametrize("seed", [11, 2 ** 33 + 5, 977])
def test_simulator_control_in_the_programs_place_is_not_correct(seed):
    res = tiny.sweep(seed=seed, control=True)
    assert not res["correct"], res["checks"]
    assert res["checks"]["mismatched_answers"]["value"] > 0
    assert res["checks"]["mismatched_counts"]["value"] == 0
    assert res["checks"]["mismatched_golden"]["value"] == 0
