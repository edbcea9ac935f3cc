"""Traffic twin (runtime/traffic_twin.py): scenario DSL validation, the
byte-identical-timeline determinism contract, a full same-seed replay
equivalence check, the twin.* config knobs, and the CLI's last-line-JSON
contract (what `tools/check --twin-smoke` and any caller with a deadline
read)."""

import json

import pytest

from livekit_server_tpu.config import ConfigError, load_config
from livekit_server_tpu.runtime.traffic_twin import (
    ChurnSegment,
    Incident,
    Scenario,
    ScenarioError,
    SizeClass,
    TrafficTwin,
    build_timeline,
    scenario_from_config,
    timeline_bytes,
    validate_scenario,
)

BASE_YAML = "keys:\n  k: s\n"


# -- scenario DSL -----------------------------------------------------------

def test_default_scenarios_validate():
    validate_scenario(Scenario())
    validate_scenario(Scenario.micro())
    validate_scenario(Scenario.standard())


def test_scenario_rejects_bad_shapes():
    good = Scenario.micro()
    with pytest.raises(ScenarioError):
        validate_scenario(Scenario(seed=1, segments=()))
    with pytest.raises(ScenarioError):
        validate_scenario(Scenario(
            seed=1, segments=good.segments,
            incidents=(Incident("meteor_strike", at=1, ticks=2),),
        ))
    with pytest.raises(ScenarioError):
        # Incident anchored past the end of the timeline.
        validate_scenario(Scenario(
            seed=1, segments=(ChurnSegment(ticks=10, join_rate=1.0),),
            incidents=(Incident("flash_crowd", at=50, ticks=2),),
        ))
    with pytest.raises(ScenarioError):
        validate_scenario(Scenario(
            seed=1, segments=good.segments,
            incidents=(Incident("flash_crowd", at=1, ticks=2,
                                magnitude=0.0),),
        ))
    with pytest.raises(ScenarioError):
        # Size-class weights must carry probability mass.
        validate_scenario(Scenario(
            seed=1, segments=good.segments,
            sizes=(SizeClass(0.0, 1, 2),),
        ))


def test_timeline_shape():
    sc = Scenario.standard(seed=41, ticks=60)
    events = build_timeline(sc, offered_load=1.0)
    assert events, "standard scenario produced no traffic"
    ticks = [e.tick for e in events]
    assert ticks == sorted(ticks)
    regions = {name for name, _ in sc.regions}
    kinds = {"join", "leave", "reconnect", "incident_begin", "incident_end"}
    for e in events:
        assert e.kind in kinds
        assert 0 <= e.tick < sc.total_ticks
        if e.kind == "join":
            assert e.region in regions
            assert e.participants >= 1
            # Codec mix: video rooms carry a codec, audio-only rooms opus.
            assert e.codec != "" if e.video else e.codec == "opus"
    assert any(e.kind == "incident_begin" for e in events)
    assert any(e.kind == "reconnect" for e in events)


# -- determinism contract ---------------------------------------------------

def test_timeline_bytes_deterministic():
    sc = Scenario.standard(seed=20, ticks=60)
    b1 = timeline_bytes(build_timeline(sc, 2.0))
    b2 = timeline_bytes(build_timeline(Scenario.standard(seed=20, ticks=60),
                                       2.0))
    assert b1 == b2, "same seed+load must be byte-identical"
    assert b1 != timeline_bytes(
        build_timeline(Scenario.standard(seed=21, ticks=60), 2.0)
    ), "different seed must perturb the timeline"
    assert b1 != timeline_bytes(build_timeline(sc, 4.0)), \
        "offered load is part of the derivation"


async def test_same_seed_runs_identical_slo_numbers():
    """Two full replays at one seed agree on every counter-derived SLO
    (deterministic_dict excludes the wall-clock members by design)."""
    def make():
        return TrafficTwin(
            Scenario.micro(seed=23), nodes=1,
            plane={"rooms": 8, "tracks_per_room": 4, "pkts_per_track": 8,
                   "subs_per_room": 4, "tick_ms": 10},
        )

    rep1 = await make().run(1.0)
    rep2 = await make().run(1.0)
    assert rep1.deterministic_dict() == rep2.deterministic_dict()
    assert rep1.joins_offered > 0
    assert rep1.audio_expected > 0


# -- twin.* config knobs ----------------------------------------------------

def test_twin_config_knobs_and_validation():
    cfg = load_config(yaml_text=BASE_YAML + (
        "twin:\n  enabled: true\n  seed: 7\n  ticks: 40\n"
        "  video_room_frac: 0.25\n"
    ))
    assert cfg.twin.seed == 7
    sc = scenario_from_config(cfg.twin)
    assert sc.seed == 7
    assert sc.total_ticks == 40
    assert sc.video_room_frac == 0.25

    for frag in (
        "twin:\n  nodes: 0\n",
        "twin:\n  ticks: -3\n",
        "twin:\n  probe_every: 0\n",
        "twin:\n  video_room_frac: 1.5\n",
        "twin:\n  loads: [1.0, -2.0, 3.0, 4.0]\n",
        "twin:\n  enabled: true\n  loads: [1.0, 2.0]\n",
        "twin:\n  no_such_knob: 1\n",
    ):
        with pytest.raises(ConfigError):
            load_config(yaml_text=BASE_YAML + frag)


# -- the CLI's output contract ------------------------------------------------

def _stdout_objects(capsys) -> list[dict]:
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    assert lines, "nothing on stdout"
    return [json.loads(ln) for ln in lines]    # every stdout line is JSON


def test_main_smoke_prints_one_json_line_last(capsys):
    """`--smoke`: the micro-scenario's verdict is the one stdout line and
    the exit code follows its `ok`."""
    from livekit_server_tpu.runtime import traffic_twin

    rc = traffic_twin.main(["--smoke", "--seed", "20"])
    (obj,) = _stdout_objects(capsys)
    assert rc == (0 if obj["ok"] else 1)
    assert obj["audio_gaps"] == 0 and obj["ticks"] > 0


def test_main_curve_last_json_line_wins(capsys, monkeypatch):
    """Curve mode: each finished load step goes out as a line flagged
    `partial`, so a caller killed at its deadline keeps the steps done;
    the whole curve comes last, unflagged; progress goes to stderr."""
    from livekit_server_tpu.runtime import traffic_twin

    async def fake_curve(sc, loads, *, on_step, log, **kw):
        steps = []
        for load in loads:
            log(f"progress: load x{load}")
            steps.append({"offered_load": load})
            on_step(list(steps))
        return {"seed": sc.seed, "loads": list(loads), "steps": steps,
                "capacity_knee_load": loads[-1]}

    monkeypatch.setattr(traffic_twin, "capacity_curve", fake_curve)
    assert traffic_twin.main(["--loads", "0.5,1.0,2.0"]) == 0
    out, err = capsys.readouterr()
    assert "progress: load x2.0" in err and "progress" not in out
    objs = [json.loads(ln) for ln in out.strip().splitlines()]
    *partials, last = objs
    assert [len(p["steps"]) for p in partials] == [1, 2, 3]
    assert all(p["partial"] is True for p in partials)
    assert "partial" not in last and last["capacity_knee_load"] == 2.0
    assert len(last["steps"]) == 3
