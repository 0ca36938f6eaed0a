"""Every tutorial lesson is a self-checking script; run each as a user
would (fresh subprocess, repo root on path via the lesson's own bootstrap).

The odd-numbered lessons run here and the even-numbered ones in
test_tutorial_even.py, so that `--dist loadfile` gives them two workers."""

import pathlib
import subprocess
import sys

import pytest
from conftest import CHILD_SECONDS

TUTORIAL = pathlib.Path(__file__).resolve().parent.parent / "tutorial"
LESSONS = sorted(p.name for p in TUTORIAL.glob("[0-2][0-9]_*.py"))


def run_lesson(lesson):
    proc = subprocess.run(
        [sys.executable, str(TUTORIAL / lesson)],
        capture_output=True,
        text=True,
        timeout=CHILD_SECONDS,
    )
    assert proc.returncode == 0, (
        lesson, proc.stdout[-800:], proc.stderr[-800:]
    )


def test_tutorial_is_complete():
    assert len(LESSONS) == 24


@pytest.mark.parametrize("lesson", LESSONS[0::2])
def test_lesson_runs(lesson):
    run_lesson(lesson)
