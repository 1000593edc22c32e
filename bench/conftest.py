"""The benchmark's CPU tests record their traces in their own temporary
directory: there the metric readers look for the raw trace, as they look
in ``.bench_trace`` after ``bench/run.py`` recorded one."""

import pytest

from bench import scopes


@pytest.fixture(autouse=True)
def _trace_dir_is_the_tests_own(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
