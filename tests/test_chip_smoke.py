"""`chip_smoke.py` kept alive between chip runs: its rehearsal mode (toy
sizes, no platform assertion, the same path) runs here in-process on the
CPU, and without the flag it refuses to start where there is no TPU."""

from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

import pytest

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


@pytest.mark.parametrize("may_shed", [True, False])
def test_a_shed_phase_is_printed_where_allowed_and_fails_elsewhere(
        capsys, monkeypatch, may_shed):
    """The governor pausing video mid-drive (forced here: level 3 as the
    media starts): a phase that may be shed prints what was shed and still
    holds every audio packet and SN space to account; any other phase
    fails on it."""
    from livekit_server_tpu.service import server as server_mod

    built = []
    create = server_mod.create_server
    monkeypatch.setattr(
        server_mod, "create_server",
        lambda cfg: built.append(create(cfg)) or built[-1])
    start = chip_smoke.MediaDrive.start

    def start_shed(drive):
        built[-1].room_manager.runtime.governor._set_level(3, "forced by the test")
        start(drive)

    monkeypatch.setattr(chip_smoke.MediaDrive, "start", start_shed)
    phase = chip_smoke.served_phase(
        "forced", chip_smoke.TOY, tick_ms=40, live_rooms=2, lead_ticks=20,
        ticks=40, may_shed=may_shed)
    if not may_shed:
        with pytest.raises(AssertionError, match="governor shed load"):
            asyncio.run(phase)
        return
    asyncio.run(phase)
    out = capsys.readouterr().out
    assert "the overload governor shed video, to level 3" in out
    assert "every audio packet received" in out
    assert "gap-free on the 4 audio streams (video was shed)" in out
