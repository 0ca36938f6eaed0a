"""The even-numbered tutorial lessons (see test_tutorial.py)."""

import pytest
from test_tutorial import LESSONS, run_lesson


@pytest.mark.parametrize("lesson", LESSONS[1::2])
def test_lesson_runs(lesson):
    run_lesson(lesson)
