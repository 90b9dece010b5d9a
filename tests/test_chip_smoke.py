"""chip_smoke.py rehearsed on the CPU: its one-chip and sharded paths at
the small golden size, and its refusal to report success off the TPU."""

import json
import os
import shutil
import subprocess
import sys

import chip_smoke as cs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ok_lines(text):
    return [ln for ln in text.splitlines() if '"ok"' in ln]


def test_refuses_without_a_chip(capsys):
    assert cs.main([]) == 1
    out = capsys.readouterr()
    assert not _ok_lines(out.out)
    assert "platform is tpu" in out.err


def test_refuses_outside_the_repo(tmp_path):
    """Copied alone into a directory, the script cannot run the system
    and must say nothing like success."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert not _ok_lines(r.stdout)


def test_one_chip_path_at_small_size(tmp_path, capsys):
    cs.smoke_one_chip(golden=cs.GOLDEN_TEST, data_dir=str(tmp_path))
    out = capsys.readouterr().out
    assert "check ok: powerlaw-test/default inside its golden envelope" \
        in out
    assert "check ok: row-argmax kernel ran" in out


def test_sharded_path_at_small_size(tmp_path, capsys):
    cs.smoke_sharded(golden=cs.GOLDEN_TEST, data_dir=str(tmp_path),
                     nshards=4)
    out = capsys.readouterr().out
    assert "check ok: plan arrays span 4 distinct devices" in out
    assert "bit-identical to nshards=1" in out


def test_last_line_is_the_ok_record(monkeypatch, capsys):
    """With every phase passing, the last stdout line is exactly the
    contract's record, with the device as JAX reports it."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(cs, "device_info", lambda chips: dict(dev))
    monkeypatch.setattr(cs, "check_native", lambda: None)
    monkeypatch.setattr(cs, "smoke_one_chip", lambda **kw: None)
    assert cs.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": dev}
