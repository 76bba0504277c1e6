"""chip_smoke.py on the CPU: every one-chip phase rehearsed in-process at
a tiny size (kernels interpreted, no compile cache), and the script's
refusals — no TPU without ``--rehearse``, no repository around it."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _json_lines(text):
    return [json.loads(line) for line in text.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("phase", ["engine_parity", "oracle", "served_path",
                                   "tenant_lanes"])
def test_rehearsal_phase(phase, tmp_path, capsys):
    rc = chip_smoke.main(["--rehearse", "--phase", phase,
                          "--out", str(tmp_path)])
    assert rc == 0
    lines = _json_lines(capsys.readouterr().out)
    assert [ln.get("phase") for ln in lines[:-1]] == [phase]
    assert lines[0]["ok"] is True
    import jax
    d = jax.devices()[0]
    assert lines[-1] == {"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": 1}}


def test_refuses_without_tpu(tmp_path, capsys):
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    assert chip_smoke.main(["--out", str(tmp_path)]) != 0
    assert _json_lines(capsys.readouterr().out) == []


def test_refuses_outside_the_repository(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert _json_lines(proc.stdout) == []
