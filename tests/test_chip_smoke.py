"""`chip_smoke.py` kept alive between chip runs: its rehearsal mode (toy
sizes, no platform assertion, the same path) runs here in-process on the
CPU, and without the flag it refuses to start where there is no TPU."""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


def test_rehearsal_runs_every_phase_and_ends_in_the_json_line(capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    text = "\n".join(out[:-1])
    for phase in ("default", "cfg4", "paged"):
        assert f"[{phase}] SN space continuous and gap-free" in text
        assert f"[{phase}] server stopped cleanly" in text
        assert "0 after warm-up" in text
    assert "[paged-compare outputs]" in text


def test_four_chip_rehearsal_runs_that_comparison_alone(capsys):
    assert chip_smoke.main(["--rehearse", "--chips", "4"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1])["ok"] is True
    assert "[four-chip outputs]" in "\n".join(out)
    assert not any(ln.startswith(("[default]", "[cfg4]", "[paged")) for ln in out)


def test_without_a_tpu_nothing_runs_and_nothing_is_printed(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no TPU" in captured.err
