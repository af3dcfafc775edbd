"""The benchmark entry point reports a failed section and exits non-zero."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def test_failed_section_exits_nonzero(monkeypatch, capsys):
    from benchmarks import roofline, run
    from repro.launch import compile_cache

    def boom():
        raise RuntimeError("section broke")

    monkeypatch.setattr(roofline, "run", boom)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["run", "--section", "roofline"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (0, None)
    assert "roofline/ERROR,0,RuntimeError: section broke" in \
        capsys.readouterr().out
