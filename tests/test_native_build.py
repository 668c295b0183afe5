"""The lazy native build under concurrent first use: what six test workers
do to an empty ``native/_build`` on a fresh checkout."""

import subprocess
import sys
from pathlib import Path

from predictionio_tpu.native import build as native_build

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "predictionio_tpu" / "native" / "eventlog_scanner.cpp"

_FIRST_USER = """
import ctypes, sys
from pathlib import Path
from predictionio_tpu.native import build
build.BUILD_DIR = Path(sys.argv[1])
so = build.build(Path(sys.argv[2]), "libeventscan")
ctypes.CDLL(str(so)).scan_run        # the finished artifact, loadable
print(so.name)
"""


def test_six_first_users_of_an_empty_build_dir_all_load_one_artifact(tmp_path):
    if native_build.compiler() is None:
        import pytest

        pytest.skip("no C++ compiler on PATH")
    build_dir = tmp_path / "_build"
    stale = build_dir / "libeventscan-0000000000000000.so"
    build_dir.mkdir()
    stale.write_bytes(b"an older source's artifact")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _FIRST_USER, str(build_dir), str(SRC)],
        cwd=str(ROOT), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for _ in range(6)]
    outs = [p.communicate(timeout=600) for p in procs]
    assert [p.returncode for p in procs] == [0] * 6, [e[-800:] for _, e in outs]
    names = {o.strip() for o, _ in outs}
    assert names == {native_build.artifact_path(SRC, "libeventscan").name}
    # one artifact of the current key, no temporary left, the stale key gone
    assert sorted(p.name for p in build_dir.iterdir()) == sorted(names)


def test_a_builder_whose_compile_fails_takes_the_winners_file(tmp_path,
                                                              monkeypatch):
    """The loser of the race: its own compile does not finish, the
    sibling's artifact is there by then, and is what it returns."""
    monkeypatch.setattr(native_build, "BUILD_DIR", tmp_path)
    so = native_build.artifact_path(SRC, "libeventscan")

    def sibling_wins_then_i_fail(cmd, **kw):
        so.write_bytes(b"the winner's")
        raise subprocess.CalledProcessError(1, cmd)

    monkeypatch.setattr(native_build, "compiler", lambda: "g++")
    monkeypatch.setattr(subprocess, "run", sibling_wins_then_i_fail)
    assert native_build.build(SRC, "libeventscan") == so
    assert so.read_bytes() == b"the winner's"
    assert [p.name for p in tmp_path.iterdir()] == [so.name]
