"""The comparison: sound answers pass, each control and each altered
answer fail."""

import pytest

import committees
from conftest import rehearsal_configs
from harness import correct, fixtures

CONFIGS = rehearsal_configs()


def window_of(kind: str, seed: int = 13):
    """One 4-commit window of the kind's rehearsal committee, signed and
    judged through the pool worker's calls: (committee, records,
    {control or "": {height: verdicts}})."""
    config = CONFIGS[kind]
    module = committees.load(config)
    committee = module.Committee(seed, config)
    fixtures.init_worker(seed, config)
    heights = [1, 2, 3, 4]
    plans = fixtures.plan_window(committee, seed, 0, heights)
    recs = fixtures.build_unit(list(zip(heights, plans)))
    commits = [(h, sigs) for h, sigs, _ in recs]
    answers = {
        control: dict(zip(
            heights, fixtures.reference_unit((commits, control))
        ))
        for control in ("",) + module.CONTROLS
    }
    return committee, recs, answers


@pytest.fixture(scope="module")
def window():
    return window_of("ed25519_equal")


@pytest.fixture(scope="module")
def mixed_window():
    # a seed whose window plants both an `s_ge_L` and a `high_s` row: of
    # 8 validators 2 hold secp256k1 keys, so not every window has both
    return window_of("mixed_keys", 12)


def served_from(recs, answers, committee):
    return [(
        recs,
        [ok for h, _, _ in recs for ok in answers[h]],
        [committee.quorum(h, answers[h]) for h, _, _ in recs],
    )]


@pytest.mark.parametrize("which", ["window", "mixed_window"])
def test_sound_answers_are_correct(which, request):
    committee, recs, answers = request.getfixturevalue(which)
    served = served_from(recs, answers[""], committee)
    numbers = correct.judge(served, answers[""], committee)
    checks = committee.cross_check(
        correct.sample_rows(served, 13), answers[""]
    )
    assert "rfc8032_vs_openssl" in checks
    assert ("secp256k1_plain_vs_openssl" in checks) == (
        which == "mixed_window"
    )
    numbers.update(checks)
    ok, checks = correct.verdict(numbers)
    assert ok and all(v == 0 for _, v, _ in checks)
    assert served[0][2].count(False) == 1


def test_the_sample_holds_a_bad_row_of_each_kind_served(mixed_window):
    committee, recs, answers = mixed_window
    served = served_from(recs, answers[""], committee)
    rows = correct.sample_rows(served, 13)
    assert rows == correct.sample_rows(served, 13)
    assert len(rows) == correct.SAMPLE_ROWS
    planted = {kind for _, _, plan in recs for kind in plan.values()}
    assert planted >= {"s_ge_L", "high_s"}
    assert planted == {rec[2][i] for rec, i in rows if i in rec[2]}


@pytest.mark.parametrize(
    "which,control",
    [("window", "s_range"), ("mixed_window", "s_range"),
     ("mixed_window", "low_s")],
)
def test_a_control_is_not_correct(which, control, request):
    """The reference without one guarantee, put in the program's place."""
    committee, recs, answers = request.getfixturevalue(which)
    served = served_from(recs, answers[control], committee)
    numbers = correct.judge(served, answers[""], committee)
    let = {"s_range": "s_ge_L", "low_s": "high_s"}[control]
    planted = sum(
        kind == let for _, _, plan in recs for kind in plan.values()
    )
    assert numbers["rows_wrong"] == planted >= 1
    assert not correct.verdict(numbers)[0]


@pytest.mark.parametrize("fault", ["row", "commit", "lost", "twice"])
def test_an_altered_answer_is_not_correct(window, fault):
    committee, recs, answers = window
    unit, bits, verdicts = served_from(recs, answers[""], committee)[0]
    served = [(unit, bits, verdicts)]
    if fault == "row":
        bits[5] = not bits[5]
    elif fault == "commit":
        verdicts[0] = not verdicts[0]
    elif fault == "lost":
        served = [(unit, None, None)]
    else:
        served = served * 2
    numbers = correct.judge(served, answers[""], committee)
    assert not correct.verdict(numbers)[0]
    key = {"row": "rows_wrong", "commit": "commits_wrong",
           "lost": "requests_failed", "twice": "rows_resubmitted"}[fault]
    assert numbers[key] >= 1
