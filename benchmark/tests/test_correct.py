"""The comparison: sound answers pass, the control and each altered
answer fail."""

import pytest

from harness import correct, fixtures
from reference import ed25519_plain


@pytest.fixture(scope="module")
def window():
    committee = fixtures.Committee(13, 8)
    fixtures.init_worker(13, 8)
    plans = fixtures.plan_window(13, 0, 4, 8)
    recs = [fixtures.sign_commit(committee, 1 + c, p)
            for c, p in enumerate(plans)]
    commits = [(h, sigs) for h, sigs, _ in recs]
    answer = lambda s_range: dict(zip(  # noqa: E731
        [h for h, _ in commits],
        fixtures.reference_unit((commits, s_range)),
    ))
    return committee, recs, answer(True), answer(False)


def served_from(recs, answers, powers):
    return [(
        recs,
        [ok for h, _, _ in recs for ok in answers[h]],
        [ed25519_plain.quorum(answers[h], powers) for h, _, _ in recs],
    )]


def test_sound_answers_are_correct(window):
    committee, recs, reference, _ = window
    served = served_from(recs, reference, committee.powers)
    numbers = correct.judge(served, reference, committee.powers)
    numbers["rfc8032_vs_openssl"] = correct.sample_rfc8032(
        committee, served, reference, 13
    )
    ok, checks = correct.verdict(numbers)
    assert ok and all(v == 0 for _, v, _ in checks)
    assert [v for _, vs in zip(recs, [served[0][2]]) for v in vs].count(False) == 1


def test_the_control_is_not_correct(window):
    committee, recs, reference, control = window
    served = served_from(recs, control, committee.powers)
    numbers = correct.judge(served, reference, committee.powers)
    assert numbers["rows_wrong"] >= 1
    assert not correct.verdict(numbers)[0]


@pytest.mark.parametrize("fault", ["row", "commit", "lost", "twice"])
def test_an_altered_answer_is_not_correct(window, fault):
    committee, recs, reference, _ = window
    unit, bits, verdicts = served_from(recs, reference, committee.powers)[0]
    served = [(unit, bits, verdicts)]
    if fault == "row":
        bits[5] = not bits[5]
    elif fault == "commit":
        verdicts[0] = not verdicts[0]
    elif fault == "lost":
        served = [(unit, None, None)]
    else:
        served = served * 2
    numbers = correct.judge(served, reference, committee.powers)
    assert not correct.verdict(numbers)[0]
    key = {"row": "rows_wrong", "commit": "commits_wrong",
           "lost": "requests_failed", "twice": "rows_resubmitted"}[fault]
    assert numbers[key] >= 1
