"""graftcheck runner — the repo's pre-commit / tier-1 static gate.

    python -m tools.check              # lint + devicecheck + compileall
    python -m tools.check --json       # findings as JSON on stdout
    python -m tools.check --baseline   # (re)write the committed baseline
    python -m tools.check --resnapshot # rewrite the devicecheck contracts

Exit codes: 0 clean, 1 findings (or compile errors), 2 stale baseline /
config problems. The baseline may only shrink: a baselined finding that
no longer reproduces must be removed from the baseline file, otherwise
the run fails with the stale entries listed. The same shrink-only
contract covers inline suppressions (a `# graftcheck: disable=` that no
longer suppresses anything is itself a finding) and the devicecheck
contract baseline (a registered entry that disappears, or a committed
contract the live tree no longer matches, fails the run).
"""

from __future__ import annotations

import argparse
import compileall
import json
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="tools.check", description=__doc__)
    ap.add_argument("--baseline", action="store_true",
                    help="rewrite the baseline file from current findings")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as JSON")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip the compileall pass (pure lint)")
    ap.add_argument("--no-native", action="store_true",
                    help="skip the native toolchain smoke (build + ABI)")
    ap.add_argument("--latency", action="store_true",
                    help="also run the slow express-lane latency smoke "
                         "(tests/test_latency_smoke.py; real sockets, ~30s)")
    ap.add_argument("--twin-smoke", action="store_true",
                    help="also run the ~2s traffic-twin micro-scenario "
                         "end-to-end (runtime/traffic_twin.py --smoke)")
    ap.add_argument("--trace-schema", action="store_true",
                    help="also validate the trace-export schema on a tiny "
                         "traced run (telemetry/trace_export --selftest)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated subset, e.g. GC01,GC04")
    ap.add_argument("--no-devicecheck", action="store_true",
                    help="skip the abstract-eval compile-contract pass "
                         "(eval_shape + jaxpr audit of the @device_entry "
                         "registry; needs jax importable)")
    ap.add_argument("--resnapshot", action="store_true",
                    help="rewrite tools/devicecheck_baseline.json from "
                         "the live tree (the sanctioned way to land an "
                         "intentional contract change)")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(REPO_ROOT))
    from livekit_server_tpu.analysis import (
        core,
        diff_baseline,
        load_baseline,
        load_project,
        run_all,
        write_baseline,
    )

    t0 = time.perf_counter()
    config = core.load_config(REPO_ROOT)
    rules = None
    if args.rules:
        rules = [r.strip().upper() for r in args.rules.split(",") if r.strip()]
        bad = [r for r in rules if r not in core.RULES]
        if bad:
            print(f"unknown rules: {', '.join(bad)}", file=sys.stderr)
            return 2
    project = load_project(REPO_ROOT, config.paths)
    stale_suppressions: list[core.Finding] = []
    findings = run_all(project, config, rules,
                       stale_suppressions=stale_suppressions)

    baseline_path = REPO_ROOT / config.baseline
    if args.baseline:
        write_baseline(baseline_path, findings, project)
        print(f"baseline written: {len(findings)} finding(s) -> "
              f"{config.baseline}")
        return 0

    new, stale = diff_baseline(findings, load_baseline(baseline_path), project)
    # Stale inline suppressions ride the same shrink-only contract as
    # the baseline: a disable= that suppresses nothing must go.
    new = list(new) + stale_suppressions

    # Abstract-eval compile contracts over the @device_entry registry
    # (eval_shape + jaxpr cost + donation audit at canonical dims).
    device_findings: list[core.Finding] = []
    device_stale: list[str] = []
    device_s = 0.0
    if not args.no_devicecheck:
        try:
            import jax  # noqa: F401  (pay the import before the timer)

            from livekit_server_tpu.analysis import devicecheck
        except ImportError as exc:   # jax absent: the AST gates still ran
            print(f"devicecheck: skipped (jax unavailable: {exc})",
                  file=sys.stderr)
            devicecheck = None
        if devicecheck is not None:
            d0 = time.perf_counter()
            device_findings, device_stale = devicecheck.run_check(
                REPO_ROOT, resnapshot=args.resnapshot
            )
            device_s = time.perf_counter() - d0
        if args.resnapshot:
            print(f"devicecheck baseline rewritten "
                  f"({device_s:.2f}s) -> tools/devicecheck_baseline.json")
        new.extend(device_findings)

    # Bytecode-compile the tree: catches syntax errors in files the
    # analyzers never import (plugins, dead branches) — cheap and total.
    compiled_ok = True
    if not args.no_compile:
        compiled_ok = compileall.compile_dir(
            str(REPO_ROOT / "livekit_server_tpu"), quiet=2, force=False
        )

    # Native toolchain smoke: compile every native/*.cpp, load the .so's,
    # cross-check the baked ABI version symbols against the ctypes layer,
    # and run one tiny build/walk through each library. Catches a broken
    # compiler, a stale .so after an ABI bump, and signature drift —
    # failures the pure-Python gates above can't see.
    native_failures: list[str] = []
    if not args.no_native:
        try:
            from livekit_server_tpu import native as native_mod

            native_failures = native_mod.native_smoke()
        except Exception as exc:  # toolchain totally absent ⇒ report, fail
            native_failures = [f"native smoke crashed: {exc!r}"]
        # Egress shard planner × paged extents: the munge/seal walk cuts
        # on room boundaries; with the paged plane, a room's entry count
        # tracks its RAGGED page extent, not the dense axis. Verify the
        # planner still tiles exactly and never splits a room when fed an
        # extent-skewed entry distribution from a real pager.
        native_failures.extend(_pager_shard_smoke())
        # Ragged paged-tick kernel: interpret-mode compile + run on a
        # tiny page table, decide bits cross-checked vs the fallback.
        native_failures.extend(_paged_kernel_smoke())

    # Opt-in latency smoke: the slow-marked express-lane wire-p99 test
    # (excluded from tier-1 by the `slow` marker). Runs in a subprocess
    # so a hung serving loop can't wedge the gate. Every child of this
    # gate is a CPU check and is held to the CPU: this process may hold
    # the chip, and a chip belongs to one process.
    latency_failures: list[str] = []
    if args.latency:
        import os
        import subprocess

        proc = subprocess.run(
            [sys.executable, "-m", "pytest",
             "tests/test_latency_smoke.py", "-q", "-m", "slow",
             "-p", "no:cacheprovider"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if proc.returncode != 0:
            tail = "\n".join((proc.stdout or "").splitlines()[-15:])
            latency_failures = [f"latency smoke failed "
                                f"(exit {proc.returncode}):\n{tail}"]
    native_failures.extend(latency_failures)

    # Opt-in trace-schema gate: run a tiny CPU plane with tracing on,
    # export the span ring as Chrome trace JSON, and validate required
    # fields + strict span nesting. Subprocess for the same hang-proofing
    # as the latency smoke.
    if args.trace_schema:
        import os
        import subprocess

        proc = subprocess.run(
            [sys.executable, "-m",
             "livekit_server_tpu.telemetry.trace_export", "--selftest"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if proc.returncode != 0:
            tail = "\n".join((proc.stdout or "").splitlines()[-15:])
            native_failures.append(
                f"trace schema selftest failed "
                f"(exit {proc.returncode}):\n{tail}"
            )

    # Opt-in traffic-twin smoke: the micro-scenario (one churn segment,
    # one flash crowd) replayed end-to-end through a real single-node
    # server in virtual time. Exit 0 requires zero audio gaps, zero
    # duplicate wire packets, and at least one admitted join. Subprocess
    # for the same hang-proofing as the latency smoke.
    if args.twin_smoke:
        import os
        import subprocess

        proc = subprocess.run(
            [sys.executable, "-m",
             "livekit_server_tpu.runtime.traffic_twin", "--smoke"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        if proc.returncode != 0:
            tail = "\n".join((proc.stdout or "").splitlines()[-15:])
            native_failures.append(
                f"twin smoke failed (exit {proc.returncode}):\n{tail}"
            )

    if args.as_json:
        print(json.dumps({
            "findings": [vars(f) for f in new],
            "stale_baseline": stale,
            "stale_device_contracts": device_stale,
            "compile_ok": bool(compiled_ok),
            "native_failures": native_failures,
        }, indent=1))
    else:
        for f in new:
            print(f.render())
        for e in stale:
            print(f"STALE baseline entry (fixed? remove it): "
                  f"{e.get('rule')} {e.get('path')}: {e.get('content')}")
        for name in device_stale:
            print(f"STALE device contract (entry gone? --resnapshot): "
                  f"{name}")
        if not compiled_ok:
            print("compileall: errors (see above)")
        for msg in native_failures:
            print(f"native: {msg}")
        dt = time.perf_counter() - t0
        ok = not (new or stale or device_stale or native_failures) \
            and compiled_ok
        status = "clean" if ok else "FAILED"
        print(f"graftcheck: {len(new)} finding(s), {len(stale)} stale "
              f"baseline entr(ies), {len(device_stale)} stale device "
              f"contract(s), {len(native_failures)} native failure(s), "
              f"{len(project.files)} files in {dt:.2f}s "
              f"(devicecheck {device_s:.2f}s) — {status}")

    if stale or device_stale:
        return 2
    if new or not compiled_ok or native_failures:
        return 1
    return 0


def _pager_shard_smoke() -> list[str]:
    """Cross-check the egress plane's entry planner against paged room
    extents: allocate a mixed-size room population through a RoomPager,
    synthesize a room-ascending egress entry column where each room's
    entry count equals its paged sub extent, and assert for several
    shard widths that the plan (a) tiles [0, n) with no gap or overlap
    and (b) keeps every room on exactly one shard. Pure host math —
    runs even when the C++ toolchain is absent."""
    import numpy as np

    from livekit_server_tpu.runtime.egress_plane import EgressPlane
    from livekit_server_tpu.runtime.pager import RoomPager

    failures: list[str] = []
    pager = RoomPager(rooms=32, tracks=16, subs=32, tpage=4, spage=8,
                      pool_pages=64)
    # 80/15/5-ish population: mostly tiny rooms, a few big ones.
    sizes = [(1, 2)] * 12 + [(2, 10)] * 4 + [(8, 30)] * 2
    for row, (tr, sb) in enumerate(sizes):
        pager.alloc_room(row, tracks=tr, subs=sb)
    rooms_col = np.concatenate([
        np.full(pager.extent(row).subs, row, np.int32)
        for row, _ in enumerate(sizes)
    ])
    for shards in (1, 2, 3, 5, 8):
        plane = EgressPlane(shards=shards, multicast_seal=False)
        lo, hi = plane.entry_plan(rooms_col)
        if lo[0] != 0 or hi[-1] != len(rooms_col) or not (lo[1:] == hi[:-1]).all():
            failures.append(
                f"pager shard smoke: entry_plan({shards}) does not tile "
                f"[0, {len(rooms_col)}): lo={lo.tolist()} hi={hi.tolist()}"
            )
            continue
        for a, b in zip(lo, hi):
            seg = rooms_col[a:b]
            if len(seg) == 0:
                continue
            prev_seg = rooms_col[:a]
            if len(prev_seg) and prev_seg[-1] == seg[0]:
                failures.append(
                    f"pager shard smoke: shards={shards} splits room "
                    f"{int(seg[0])} across a cut at entry {int(a)}"
                )
    return failures


def _paged_kernel_smoke() -> list[str]:
    """Compile-and-run the ragged paged-tick kernel (ops/paged_kernel.py)
    in Pallas interpret mode on a tiny hand-built page table, and check
    the forward decision bits against the gathered CPU fallback. Catches
    a kernel that no longer traces (Mosaic/Pallas API drift) and decide
    algebra divergence, without needing a TPU."""
    import numpy as np

    try:
        import jax.numpy as jnp

        from livekit_server_tpu.models import paged, plane
        from livekit_server_tpu.ops import paged_kernel

        PD = paged.PagedDims(rooms=2, tracks=4, pkts=2, subs=8,
                             tpage=2, spage=4, pool_pages=8)
        P, TP, K, SP = 8, 2, 2, 4
        st = plane.init_state(PD.pooled())
        sub = np.zeros((P, TP, SP), bool)
        sub[[0, 2, 3]] = True
        pub = np.zeros((P, TP), bool)
        pub[[0, 2, 3]] = True
        st = st._replace(
            meta=st.meta._replace(published=jnp.asarray(pub)),
            ctrl=st.ctrl._replace(subscribed=jnp.asarray(sub)),
        )
        rng = np.random.default_rng(11)
        z = lambda sh, dt=np.int32: jnp.zeros(sh, dt)
        inp = plane.TickInputs(
            sn=jnp.asarray(rng.integers(0, 1000, (P, TP, K)), jnp.int32),
            ts=z((P, TP, K)), layer=z((P, TP, K)), temporal=z((P, TP, K)),
            keyframe=z((P, TP, K), bool), layer_sync=z((P, TP, K), bool),
            begin_pic=z((P, TP, K), bool), end_frame=z((P, TP, K), bool),
            pid=z((P, TP, K)), tl0=z((P, TP, K)), keyidx=z((P, TP, K)),
            size=jnp.full((P, TP, K), 100, jnp.int32),
            frame_ms=z((P, TP, K)), audio_level=z((P, TP, K)),
            arrival_rtp=z((P, TP, K)), ts_jump=z((P, TP, K)),
            valid=jnp.ones((P, TP, K), bool),
            estimate=z((P, SP), np.float32),
            estimate_valid=z((P, SP), bool), nacks=z((P, SP), np.float32),
            pub_rtt_ms=z((P, TP), np.float32),
            fb_delay_ms=z((P, SP), np.float32),
            fb_recv_bps=z((P, SP), np.float32), fb_valid=z((P, SP), bool),
            fb_enabled=z((P, SP), bool), sub_reset=z((P, SP), bool),
            pad_num=z((P, SP)), pad_track=z((P, SP)) - 1,
            tick_ms=jnp.asarray(10, jnp.int32),
            roll_quality=jnp.asarray(0, jnp.int32),
        )
        base = st.ctrl.subscribed & ~st.ctrl.sub_muted & (
            st.meta.published & ~st.meta.pub_muted)[:, :, None]
        live = np.array([0, 2, 3, 0], np.int32)  # pow2-padded live rows
        ik = paged_kernel.decide_pages(
            st.sel, st.meta.is_svc, st.meta.is_video, base, inp, live,
            wire_overhead=42, use_pallas=False, interpret=True)
        fb = paged_kernel.decide_pages(
            st.sel, st.meta.is_svc, st.meta.is_video, base, inp, live,
            wire_overhead=42, use_pallas=False)
        for f in ("send_bits", "drop_bits", "need_kf", "pkts_sent"):
            a, b = np.asarray(getattr(ik, f)), np.asarray(getattr(fb, f))
            if not np.array_equal(a, b):
                return [f"paged kernel smoke: interpret vs fallback "
                        f"diverge on {f}"]
    except Exception as exc:
        return [f"paged kernel smoke crashed: {exc!r}"]
    return []


if __name__ == "__main__":
    raise SystemExit(main())
