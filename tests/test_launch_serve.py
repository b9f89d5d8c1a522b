"""``python -m repro.launch.serve`` exits non-zero when any served answer
differs from the host path."""

from __future__ import annotations

import sys

import numpy as np
import pytest

from repro.launch import serve
from repro.serving.engine import BatchedServer

ARGV = ["serve", "--articles", "2", "--versions", "4", "--queries", "6",
        "--mode", "and"]


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    # the CLI turns on the persistent cache; tests keep it off
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: "off")


@pytest.mark.parametrize("extra", [[], ["--frontend"]])
def test_serve_passes_when_answers_agree(monkeypatch, capsys, extra):
    monkeypatch.setattr(sys, "argv", ARGV + extra)
    serve.main()
    out = capsys.readouterr().out
    assert "host/planned agreement: 6/6" in out
    if extra:
        assert "host/frontend agreement: 6/6" in out


def test_serve_fails_on_a_wrong_device_answer(monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ARGV)
    monkeypatch.setattr(BatchedServer, "conjunctive",
                        lambda self, queries, width=None:
                        [np.asarray([10**6]) for _ in queries])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert "differ from the host path" in str(exc.value.code)
    assert "host/planned agreement: 0/6" in capsys.readouterr().out
