"""The reference flags a stale, missing or altered answer."""
import numpy as np

from perfbench import reference

W = 256


def answers(keys, versions):
    rows = reference.rows(keys, versions, W)
    return rows, np.ones(len(keys), bool)


def compare(check, keys, want, rows, found):
    idx = np.arange(len(keys))
    check.compare(keys, want, found, rows[:, :2], idx, rows[idx], W)


def test_rows_encode_key_and_version_exactly():
    keys = np.array([0, 1, (1 << 20) - 1])
    rows = reference.rows(keys, 1 << 23, W)
    assert rows.dtype == np.float32 and rows.shape == (3, W)
    np.testing.assert_array_equal(rows[:, 0], keys)
    np.testing.assert_array_equal(rows[:, 1], 1 << 23)
    assert (rows < 1 << 24).all() and (rows >= 0).all()
    assert (reference.rows(keys, 5, W) != reference.rows(keys, 6, W))[
        :, 2:].any(axis=1).all()


def test_latest_version_answers_are_correct():
    ref, check = reference.Reference(64), reference.Check()
    keys = np.arange(8)
    ref.put(keys, 3)
    ref.put(keys[:4], 4)
    want = ref.expected(keys)
    compare(check, keys, want, *answers(keys, want))
    assert check.correct and check.stale_or_lost == 0


def test_stale_missing_and_altered_answers_are_flagged():
    ref = reference.Reference(64)
    keys = np.arange(8)
    ref.put(keys, 3)
    ref.put(keys[:2], 4)
    want = ref.expected(keys)

    stale = reference.Check()
    rows, found = answers(keys, np.full(8, 3))        # missed the update
    compare(stale, keys, want, rows, found)
    assert stale.stale_or_lost == 2 and not stale.correct

    missing = reference.Check()
    rows, found = answers(keys, want)
    found[5] = False
    compare(missing, keys, want, rows, found)
    assert missing.stale_or_lost == 1 and not missing.correct

    altered = reference.Check()
    rows, found = answers(keys, want)
    rows[6, 100] += 1
    compare(altered, keys, want, rows, found)
    assert altered.stale_or_lost == 0 and altered.bad_rows == 1
    assert not altered.correct


def test_a_key_never_written_is_a_wrong_answer_even_if_found():
    ref, check = reference.Reference(16), reference.Check()
    keys = np.array([1])
    compare(check, keys, ref.expected(keys), *answers(keys, [0]))
    assert not check.correct
