"""tools/bench_gate.py — the tier-1 gate on the BENCH artifact trajectory:
perf regressions and silently-degraded artifacts fail loudly, loudly-
degraded runs skip, and the in-tree trajectory itself must gate clean."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import bench_gate  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, name, parsed, n=None, rc=0):
    doc = {"n": n if n is not None else bench_gate._round_of(name),
           "cmd": "python bench.py", "rc": rc, "tail": "", "parsed": parsed}
    path = tmp_path / name
    path.write_text(json.dumps(doc))
    return str(path)


def _half(value, *, metric="resnet50_images_per_sec_per_chip",
          platform="tpu", degraded=None, **extra):
    half = {"metric": metric, "value": value, "unit": "images/sec/chip",
            "vs_baseline": round(value / 2000.0, 4), "platform": platform,
            "mem_bw_gbps": 700.0, "ici_bw_gbps": 40.0}
    if degraded:
        half["degraded"] = degraded
    half.update(extra)
    return half


# -- the acceptance check: a recorded trajectory gates clean ------------------


@pytest.fixture()
def recorded_trajectory(tmp_path):
    """The shapes of the first five recorded rounds — an empty failure, two
    healthy on-chip runs (the second with its wide_deep half), a timeout
    with no number, and a LOUDLY degraded CPU fallback — written as
    fixtures: records are not kept in the tree."""
    def early(value, **kw):  # rounds before the roofline stamps
        half = _half(value, **kw)
        del half["mem_bw_gbps"], half["ici_bw_gbps"]
        return half

    resnet = dict(n_chips=1, batch_size=128, loss=4.8477, mfu=0.2989,
                  flops_per_step=3060675379200.0)
    wide = early(39.45, metric="wide_deep_steps_per_sec", n_chips=1,
                 batch_size=4096, loss=0.0, mfu=0.0076,
                 unit="steps/sec", vs_baseline=0.3945)
    degraded = "accelerator unavailable: liveness probe failed: timeout " \
               "after 60s"
    cpu_wide = early(4262.56, metric="wide_deep_steps_per_sec",
                     platform="cpu", degraded=degraded, batch_size=16,
                     unit="steps/sec", vs_baseline=42.6256)

    _write(tmp_path, "BENCH_r01.json", None, rc=1)
    _write(tmp_path, "BENCH_r02.json", early(2462.82, **resnet))
    _write(tmp_path, "BENCH_r03.json",
           early(2462.3, secondary=wide, **resnet))
    _write(tmp_path, "BENCH_r04.json", None, rc=124)
    _write(tmp_path, "BENCH_r05.json",
           early(6689.02, platform="cpu", degraded=degraded, batch_size=16,
                 secondary=cpu_wide,
                 probe={"ok": False, "error": "timeout after 60s"}))
    return str(tmp_path)


def test_in_tree_trajectory_produces_machine_readable_verdict(
        recorded_trajectory):
    paths = bench_gate.discover(recorded_trajectory)
    assert len(paths) == 5
    verdict = bench_gate.gate(paths)
    # round-trips through strict JSON (machine-readable contract)
    assert json.loads(json.dumps(verdict))["verdict"] == verdict["verdict"]
    # a recorded history must never fail the gate: r05 is LOUDLY degraded
    # (skip), r01/r04 are prior-round empties (warn)
    assert verdict["verdict"] in ("pass", "skip")
    assert verdict["reasons"] == []


def test_in_tree_artifacts_all_schema_validate(recorded_trajectory):
    for path in bench_gate.discover(recorded_trajectory):
        art = bench_gate.load_artifact(path)
        assert art["problems"] == [], f"{path}: {art['problems']}"
        if art["parsed"] is None:
            continue
        for label, half in bench_gate.halves(art["parsed"]):
            require = art["n"] >= bench_gate.DEFAULT_REQUIRE_ROOFLINE_FROM
            problems = bench_gate.validate_half(
                half, require_roofline=require)
            assert problems == [], f"{path}:{label}: {problems}"


# -- crafted trajectories ----------------------------------------------------


def test_healthy_trajectory_passes(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r01.json", _half(2400.0)),
        _write(tmp_path, "BENCH_r02.json", _half(2450.0)),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass"
    assert verdict["newest"] == "BENCH_r02.json"
    assert any(c["name"].startswith("regression:") and c["status"] == "pass"
               for c in verdict["checks"])


def test_regression_fails(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r01.json", _half(2400.0)),
        _write(tmp_path, "BENCH_r02.json", _half(1200.0)),  # half the perf
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("regression" in r for r in verdict["reasons"])


def test_degraded_newest_skips_and_prior_degraded_not_compared(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r01.json", _half(2400.0)),
        # a degraded CPU-fallback round between the healthy ones
        _write(tmp_path, "BENCH_r02.json",
               _half(6000.0, platform="cpu", degraded="probe failed")),
        _write(tmp_path, "BENCH_r03.json",
               _half(100.0, platform="cpu", degraded="probe failed")),
    ]
    verdict = bench_gate.gate(paths)
    # newest is loudly degraded: no perf judgment possible
    assert verdict["verdict"] == "skip"
    assert verdict["reasons"] == []


def test_half_degraded_newest_skips_not_passes(tmp_path):
    """A degraded primary with a healthy secondary is NOT a clean pass:
    the headline number is fallback evidence with no regression
    judgment — the verdict must say skip."""
    wd = _half(103.0, metric="wide_deep_steps_per_sec")
    wd["vs_baseline"] = 1.03
    mixed = dict(_half(6000.0, platform="cpu", degraded="probe failed"),
                 secondary=wd)
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r01.json", mixed)])
    assert verdict["verdict"] == "skip"
    assert verdict["reasons"] == []


def test_silently_degraded_newest_fails(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r01.json", _half(2400.0)),
        _write(tmp_path, "BENCH_r02.json", None, rc=124),  # the r04 mode
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("silently degraded" in r for r in verdict["reasons"])


def test_prior_empty_rounds_only_warn(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r01.json", None, rc=1),
        _write(tmp_path, "BENCH_r02.json", _half(2400.0)),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass"
    assert any(c["status"] == "warn" for c in verdict["checks"])


def test_target_floor_breach_fails(tmp_path):
    paths = [_write(tmp_path, "BENCH_r01.json", _half(100.0))]  # vs 2000
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("target" in r for r in verdict["reasons"])


def test_roofline_fields_required_from_round_6(tmp_path):
    half = _half(2400.0)
    del half["mem_bw_gbps"], half["ici_bw_gbps"]
    # round 5: grandfathered
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r05.json", dict(half))])
    assert verdict["verdict"] == "pass"
    # round 6+: the schema is total — measure or stamp null + reason
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r06.json", dict(half))])
    assert verdict["verdict"] == "fail"
    assert any("mem_bw_gbps" in r for r in verdict["reasons"])
    # explicit null + reason is fine
    ok = dict(half, mem_bw_gbps=None, mem_bw_reason="probe crashed",
              ici_bw_gbps=None, ici_bw_reason="single device")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r06.json", ok)])
    assert verdict["verdict"] == "pass"


def _feed_fields(rps=2000.0, transport="shm", **extra):
    fields = {"feed_rows_per_sec": rps, "feed_transport": transport,
              "feed_rows_per_sec_pickle": rps / 3.5,
              "feed_transport_speedup": 3.5,
              "feed_rows_total": 4096,
              "feed_chunk_rows": 256, "feed_batch_size": 1024,
              "feed_row_bytes": 65544}
    fields.update(extra)
    return fields


def test_feed_field_required_on_primary_from_round_7(tmp_path):
    # round 6: grandfathered
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r06.json", _half(2400.0))])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 7+: the primary must carry the feed microbench
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r07.json", _half(2400.0))])
    assert verdict["verdict"] == "fail"
    assert any("feed_rows_per_sec" in r for r in verdict["reasons"])
    # measured value + transport attribution satisfies
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r07.json", _half(2400.0, **_feed_fields()))])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies too (degraded host, spent budget)
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r07.json",
        _half(2400.0, feed_rows_per_sec=None,
              feed_transport_reason="wall budget exhausted"))])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # the secondary half never needs it (stamped once per run)
    wd = _half(103.0, metric="wide_deep_steps_per_sec")
    wd["vs_baseline"] = 1.03
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r07.json",
        dict(_half(2400.0, **_feed_fields()), secondary=wd))])
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_feed_value_without_transport_attribution_fails(tmp_path):
    fields = _feed_fields()
    del fields["feed_transport"]
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r07.json", _half(2400.0, **fields))])
    assert verdict["verdict"] == "fail"
    assert any("feed_transport" in r for r in verdict["reasons"])


def test_feed_regression_gated_within_same_transport(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r06.json",
               _half(2400.0, **_feed_fields(rps=2000.0))),
        _write(tmp_path, "BENCH_r07.json",
               _half(2400.0, **_feed_fields(rps=500.0))),  # data plane 4× off
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("feed_rows_per_sec" in r and "data plane" in r
               for r in verdict["reasons"])


def test_feed_not_compared_across_transports_or_configs(tmp_path):
    # transport changed (shm host → pickle fallback host): different
    # experiment, no regression judgment in either direction
    paths = [
        _write(tmp_path, "BENCH_r06.json",
               _half(2400.0, **_feed_fields(rps=2000.0))),
        _write(tmp_path, "BENCH_r07.json",
               _half(2400.0, **_feed_fields(
                   rps=500.0, transport="pickle",
                   feed_transport_reason="shm unavailable"))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    assert any(c["name"] == "regression:feed_rows_per_sec"
               and "no comparable prior" in c["detail"]
               for c in verdict["checks"])
    # feed config changed (row size sweep): also incomparable
    paths = [
        _write(tmp_path, "BENCH_r06.json",
               _half(2400.0, **_feed_fields(rps=2000.0))),
        _write(tmp_path, "BENCH_r07.json",
               _half(2400.0, **_feed_fields(rps=500.0, feed_row_bytes=264))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # total row count is config identity too: per-run fixed cost (manager
    # startup/teardown) amortizes over rows_total, so rows/sec at a
    # different total is a different experiment
    paths = [
        _write(tmp_path, "BENCH_r06.json",
               _half(2400.0, **_feed_fields(rps=2000.0))),
        _write(tmp_path, "BENCH_r07.json",
               _half(2400.0, **_feed_fields(rps=500.0,
                                            feed_rows_total=1024))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_feed_prior_from_degraded_round_still_compared(tmp_path):
    """The feed number is host-side: a CPU-fallback (degraded) prior still
    measured the same data plane and still counts as a prior."""
    degraded_prior = _half(6000.0, platform="cpu", degraded="probe failed",
                           **_feed_fields(rps=2000.0))
    healthy_bad_feed = _half(2400.0, **_feed_fields(rps=500.0))
    paths = [
        _write(tmp_path, "BENCH_r06.json", degraded_prior),
        _write(tmp_path, "BENCH_r07.json", healthy_bad_feed),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("feed_rows_per_sec" in r for r in verdict["reasons"])


def test_feed_regression_judged_even_on_degraded_newest(tmp_path):
    """Symmetric case: when the NEWEST run's accelerator half degraded, its
    host-side feed measurement is still performance evidence — the degraded
    skip must not short-circuit the feed regression judgment."""
    healthy_prior = _half(2400.0, **_feed_fields(rps=2000.0))
    degraded_bad_feed = _half(600.0, platform="cpu", degraded="probe failed",
                              **_feed_fields(rps=500.0))
    paths = [
        _write(tmp_path, "BENCH_r06.json", healthy_prior),
        _write(tmp_path, "BENCH_r07.json", degraded_bad_feed),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("feed_rows_per_sec" in r and "data plane" in r
               for r in verdict["reasons"])


def _serve_fields(rps=300000.0, ingest="arrow", **extra):
    fields = {"serve_rows_per_sec": rps, "serve_ingest": ingest,
              "serve_rows_per_sec_legacy": rps / 3.5,
              "serve_speedup": 3.5, "serving_compiles_total": 2,
              "serve_rows_total": 16384, "serve_batch_size": 1024,
              "serve_row_bytes": 1032, "serve_bucket_sizes": [256, 1024]}
    fields.update(extra)
    return fields


def _r8(**extra):
    """A round-8-complete primary half (feed + serving stamped)."""
    return _half(2400.0, **_feed_fields(), **_serve_fields(**extra))


def test_serving_field_required_on_primary_from_round_8(tmp_path):
    # round 7: grandfathered
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r07.json", _half(2400.0, **_feed_fields()))])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 8+: the primary must carry the serving microbench
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r08.json", _half(2400.0, **_feed_fields()))])
    assert verdict["verdict"] == "fail"
    assert any("serve_rows_per_sec" in r for r in verdict["reasons"])
    # measured value + ingest attribution satisfies
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r08.json", _r8())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies too
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r08.json",
        _half(2400.0, **_feed_fields(), serve_rows_per_sec=None,
              serve_reason="wall budget exhausted"))])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # the secondary half never needs it (stamped once per run)
    wd = _half(103.0, metric="wide_deep_steps_per_sec")
    wd["vs_baseline"] = 1.03
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r08.json", dict(_r8(), secondary=wd))])
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_serving_value_without_ingest_attribution_fails(tmp_path):
    fields = _serve_fields()
    del fields["serve_ingest"]
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r08.json",
        _half(2400.0, **_feed_fields(), **fields))])
    assert verdict["verdict"] == "fail"
    assert any("serve_ingest" in r for r in verdict["reasons"])


def test_serving_regression_gated_within_same_geometry(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r08.json", _r8(rps=300000.0)),
        _write(tmp_path, "BENCH_r09.json", _r8(rps=60000.0)),  # 5× off
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("serve_rows_per_sec" in r and "serving data plane" in r
               for r in verdict["reasons"])


def test_serving_not_compared_across_ingest_or_geometry(tmp_path):
    # ingest representation changed (arrow → rows fallback): different
    # experiment, no regression judgment in either direction
    paths = [
        _write(tmp_path, "BENCH_r07.json", _r8(rps=300000.0)),
        _write(tmp_path, "BENCH_r08.json", _r8(rps=60000.0, ingest="rows")),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    assert any(c["name"] == "regression:serve_rows_per_sec"
               and "no comparable prior" in c["detail"]
               for c in verdict["checks"])
    # bucket geometry changed: also incomparable (padding waste and
    # compile count are properties of the bucket set)
    paths = [
        _write(tmp_path, "BENCH_r07.json", _r8(rps=300000.0)),
        _write(tmp_path, "BENCH_r08.json",
               _r8(rps=60000.0, serve_bucket_sizes=[1024])),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_serving_regression_judged_even_on_degraded_newest(tmp_path):
    """The serving number is host-side: a degraded accelerator half must
    not short-circuit its regression judgment (same rule as feed)."""
    degraded_bad = dict(
        _half(600.0, platform="cpu", degraded="probe failed",
              **_feed_fields(), **_serve_fields(rps=60000.0)))
    paths = [
        _write(tmp_path, "BENCH_r07.json", _r8(rps=300000.0)),
        _write(tmp_path, "BENCH_r08.json", degraded_bad),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("serve_rows_per_sec" in r for r in verdict["reasons"])


def test_rebaselined_batch_size_not_compared_across_configs(tmp_path):
    """The wide_deep re-baseline pins batch 1024; steps/sec at batch 4096
    is a different experiment — neither direction may read as a
    regression (BASELINE.md 'wide_deep re-baseline')."""
    old = _half(103.0, metric="wide_deep_steps_per_sec", batch_size=1024)
    old["vs_baseline"] = 1.03
    new = _half(43.0, metric="wide_deep_steps_per_sec", batch_size=4096)
    new["vs_baseline"] = 0.43
    paths = [
        _write(tmp_path, "BENCH_r01.json", old),
        _write(tmp_path, "BENCH_r02.json", new),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    assert any("no comparable prior" in c["detail"]
               for c in verdict["checks"]
               if c["name"].startswith("regression:"))


def test_timing_suspect_priors_excluded_from_comparison(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r01.json",
               _half(99999.0, timing_suspect=True)),
        _write(tmp_path, "BENCH_r02.json", _half(2400.0)),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass"


def test_secondary_half_judged_too(tmp_path):
    wd_prior = _half(100.0, metric="wide_deep_steps_per_sec")
    wd_prior["vs_baseline"] = 1.0
    wd_bad = _half(10.0, metric="wide_deep_steps_per_sec")
    wd_bad["vs_baseline"] = 0.1
    paths = [
        _write(tmp_path, "BENCH_r01.json",
               dict(_half(2400.0), secondary=wd_prior)),
        _write(tmp_path, "BENCH_r02.json",
               dict(_half(2400.0), secondary=wd_bad)),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("wide_deep" in r for r in verdict["reasons"])


def test_cli_exit_codes(tmp_path):
    gate_py = os.path.join(REPO, "tools", "bench_gate.py")
    ok = _write(tmp_path, "BENCH_r01.json", _half(2400.0))
    proc = subprocess.run([sys.executable, gate_py, ok],
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    assert json.loads(proc.stdout)["verdict"] == "pass"
    bad = _write(tmp_path, "BENCH_r02.json", _half(10.0))
    proc = subprocess.run([sys.executable, gate_py, ok, bad],
                          capture_output=True, text=True)
    assert proc.returncode == 1
    assert json.loads(proc.stdout)["verdict"] == "fail"
    proc = subprocess.run(
        [sys.executable, gate_py, "--repo", str(tmp_path / "empty")],
        capture_output=True, text=True)
    assert proc.returncode == 2


# -- flight-recorder stage breakdowns (required from r09) --------------------


def _flight_bd(frac=1.0, verdict="feed_starved", wall=10.0, **extra):
    bd = {"wall_s": wall, "stage_sum_s": round(wall * frac, 4),
          "stage_sum_frac": round(frac, 4),
          "stages_s": {"wait": round(wall * frac * 0.8, 4),
                       "ingest": round(wall * frac * 0.2, 4)},
          "overlapped_stages_s": {}, "batches": 16,
          "verdicts": {verdict: 16}, "verdict": verdict}
    bd.update(extra)
    return bd


def _r9(**extra):
    """A round-9-complete primary half: microbenches + stage breakdowns."""
    half = _half(2400.0, **_feed_fields(), **_serve_fields())
    half["feed_stage_breakdown"] = _flight_bd()
    half["serve_stage_breakdown"] = _flight_bd(verdict="device_bound")
    half.update(extra)
    return half


def test_flight_breakdowns_required_on_primary_from_round_9(tmp_path):
    # round 8: grandfathered — no breakdown owed
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r08.json",
                _half(2400.0, **_feed_fields(), **_serve_fields()))])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 9+: both healthy microbench numbers owe their decomposition
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r09.json",
                _half(2400.0, **_feed_fields(), **_serve_fields()))])
    assert verdict["verdict"] == "fail"
    assert any("feed_stage_breakdown" in r for r in verdict["reasons"])
    assert any("serve_stage_breakdown" in r for r in verdict["reasons"])
    # complete round 9 passes
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r09.json", _r9())])
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_flight_breakdown_must_reconcile_with_wall_time(tmp_path):
    """A breakdown whose stage sum disagrees with measured wall beyond
    the tolerance fails the artifact — it is attribution, not decoration."""
    undercounts = _r9(feed_stage_breakdown=_flight_bd(frac=0.6))
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r09.json", undercounts)])
    assert verdict["verdict"] == "fail"
    assert any("does not reconcile" in r for r in verdict["reasons"])
    overcounts = _r9(serve_stage_breakdown=_flight_bd(
        frac=1.4, verdict="device_bound"))
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r09.json", overcounts)])
    assert verdict["verdict"] == "fail"
    assert any("does not reconcile" in r for r in verdict["reasons"])
    # within the ±15% tolerance: fine
    ok = _r9(feed_stage_breakdown=_flight_bd(frac=0.9))
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r09.json", ok)])
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_flight_breakdown_requires_verdict_and_numbers(tmp_path):
    no_verdict = _r9()
    del no_verdict["feed_stage_breakdown"]["verdict"]
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r09.json", no_verdict)])
    assert verdict["verdict"] == "fail"
    assert any("verdict" in r for r in verdict["reasons"])
    no_wall = _r9()
    del no_wall["serve_stage_breakdown"]["wall_s"]
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r09.json", no_wall)])
    assert verdict["verdict"] == "fail"
    assert any("wall_s" in r for r in verdict["reasons"])


def test_flight_breakdown_not_owed_for_null_metrics(tmp_path):
    """A null microbench number (already explained by its reason field)
    owes no decomposition — the schema stays total, not redundant."""
    half = _half(2400.0,
                 feed_rows_per_sec=None,
                 feed_transport_reason="wall budget exhausted",
                 serve_rows_per_sec=None,
                 serve_reason="wall budget exhausted")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r09.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_flight_breakdown_judged_when_present_before_round_9(tmp_path):
    """Same or-present semantics as the other schema fields: an early
    round that ships a breakdown is held to the reconciliation bar."""
    early = _half(2400.0, **_feed_fields(),
                  feed_stage_breakdown=_flight_bd(frac=0.5))
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r07.json", early)])
    assert verdict["verdict"] == "fail"
    assert any("does not reconcile" in r for r in verdict["reasons"])


def test_flight_breakdown_null_with_reason_is_exempt(tmp_path):
    """A run with the recorder opted out (TFOS_FLIGHT=0) cannot decompose
    its wall: explicit null + reason satisfies the r09 requirement; a
    bare null does not."""
    opted_out = _r9()
    opted_out["feed_stage_breakdown"] = None
    opted_out["feed_stage_breakdown_reason"] = \
        "flight recorder disabled (TFOS_FLIGHT=0)"
    opted_out["serve_stage_breakdown"] = None
    opted_out["serve_stage_breakdown_reason"] = \
        "flight recorder disabled (TFOS_FLIGHT=0)"
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r09.json", opted_out)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    bare_null = _r9()
    bare_null["feed_stage_breakdown"] = None
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r09.json", bare_null)])
    assert verdict["verdict"] == "fail"
    assert any("feed_stage_breakdown" in r for r in verdict["reasons"])


# -- elastic recovery (ISSUE 8) ----------------------------------------------


def _recovery_fields(seconds=14.0, **extra):
    fields = {"recovery_seconds": seconds,
              "recovery_num_executors": 3,
              "recovery_ckpt_every_steps": 4,
              "recovery_kill_at_step": 8,
              "recovery_batch_size": 32}
    fields.update(extra)
    return fields


def _r10(**extra):
    """A round-10-complete primary half: all microbenches + recovery."""
    half = _r9(**_recovery_fields())
    half.update(extra)
    return half


def test_recovery_field_required_on_primary_from_round_10(tmp_path):
    # round 9: grandfathered — no recovery number owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r09.json", _r9())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 10+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r10.json", _r9())])
    assert verdict["verdict"] == "fail"
    assert any("recovery_seconds" in r for r in verdict["reasons"])
    # complete round 10 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r10.json", _r10())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r9(recovery_seconds=None,
               recovery_reason="wall budget exhausted before recovery "
                               "microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r10.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r9(recovery_seconds=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r10.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("recovery_reason" in r for r in verdict["reasons"])


def test_recovery_value_without_config_identity_fails(tmp_path):
    half = _r9(recovery_seconds=14.0)  # number without its config
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r10.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r for r in verdict["reasons"])


def test_recovery_regression_is_lower_is_better(tmp_path):
    """recovery_seconds is a latency: a faster newest run passes, a
    slower-beyond-1/threshold newest run fails."""
    paths = [
        _write(tmp_path, "BENCH_r10.json", _r10()),
        _write(tmp_path, "BENCH_r11.json",
               _r11(**_recovery_fields(seconds=12.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    paths = [
        _write(tmp_path, "BENCH_r10.json", _r10()),
        _write(tmp_path, "BENCH_r11.json",
               _r11(**_recovery_fields(seconds=30.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("recovery slowed" in r for r in verdict["reasons"])


def test_recovery_not_compared_across_configs(tmp_path):
    """A different checkpoint cadence bounds a different amount of lost
    work: 30s at cadence 16 must not regress against 14s at cadence 4."""
    paths = [
        _write(tmp_path, "BENCH_r10.json", _r10()),
        _write(tmp_path, "BENCH_r11.json",
               _r11(**_recovery_fields(seconds=30.0,
                                       recovery_ckpt_every_steps=16))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_recovery_judged_even_on_degraded_newest(tmp_path):
    """Host-side like the feed/serving microbenches: a degraded
    accelerator half still measured the real recovery path, so its
    number stays gated."""
    paths = [
        _write(tmp_path, "BENCH_r10.json", _r10()),
        _write(tmp_path, "BENCH_r11.json",
               _r11(**_recovery_fields(seconds=40.0),
                    degraded="accelerator unavailable: probe timeout")),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("recovery slowed" in r for r in verdict["reasons"])


# -- online serving tier (ISSUE 9) -------------------------------------------


def _online_fields(rps=11000.0, p99=5.2, **extra):
    fields = {"online_rows_per_sec": rps,
              "online_rows_per_sec_uncoalesced": rps / 2.5,
              "online_speedup": 2.5,
              "online_p50_ms": 2.8, "online_p99_ms": p99,
              "online_p99_ms_uncoalesced": 21.5,
              "online_slo_ms": 500.0, "online_flush_ms": 4.0,
              "online_clients": 32, "online_rows_total": 3200,
              "online_batch_size": 64, "online_feature_dim": 256,
              "online_hidden_dim": 1024,
              "online_bucket_sizes": [16, 32, 64],
              "online_shed_total": 0,
              "online_stage_breakdown": _flight_bd(
                  verdict="device_bound",
                  stages_s={"wait": 3.0, "compute": 6.0, "reply": 1.0})}
    fields.update(extra)
    return fields


def _r11(**extra):
    """A round-11-complete primary half: all microbenches + online."""
    half = _r10(**_online_fields())
    half.update(extra)
    return half


def _r12(**extra):
    """A round-12-complete primary half: r11 + measured tracing
    overhead."""
    half = _r11(trace_overhead_frac=0.012)
    half.update(extra)
    return half


def test_online_field_required_on_primary_from_round_11(tmp_path):
    # round 10: grandfathered — no online number owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r10.json", _r10())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 11+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r11.json", _r10())])
    assert verdict["verdict"] == "fail"
    assert any("online_rows_per_sec" in r for r in verdict["reasons"])
    # complete round 11 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r11.json", _r11())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r10(online_rows_per_sec=None,
                online_reason="wall budget exhausted before online "
                              "serving microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r11.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r10(online_rows_per_sec=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r11.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("online_reason" in r for r in verdict["reasons"])


def test_online_value_without_config_identity_fails(tmp_path):
    half = _r10(online_rows_per_sec=11000.0,
                online_p99_ms=5.2, online_slo_ms=500.0,
                online_stage_breakdown=_flight_bd(verdict="device_bound"))
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r11.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r for r in verdict["reasons"])


def test_online_p99_over_slo_fails(tmp_path):
    """A throughput claimed at an SLO the run missed is not a
    measurement: p99 above online_slo_ms fails the artifact."""
    half = _r11(**_online_fields(p99=700.0))
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r11.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("SLO" in r for r in verdict["reasons"])
    # a value without its measured p99 is equally unjudgeable
    missing = _r11()
    del missing["online_p99_ms"]
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r11.json", missing)])
    assert verdict["verdict"] == "fail"
    assert any("online_p99_ms" in r for r in verdict["reasons"])


def test_online_regression_within_same_config(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r11.json", _r11()),
        _write(tmp_path, "BENCH_r12.json",
               _r12(**_online_fields(rps=10500.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    paths = [
        _write(tmp_path, "BENCH_r11.json", _r11()),
        _write(tmp_path, "BENCH_r12.json",
               _r12(**_online_fields(rps=5000.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("online tier regressed" in r for r in verdict["reasons"])


def test_online_not_compared_across_slo_or_geometry(tmp_path):
    """rows/sec at a looser SLO (or different client count) is a
    different experiment — never regression-compared."""
    paths = [
        _write(tmp_path, "BENCH_r11.json", _r11()),
        _write(tmp_path, "BENCH_r12.json",
               _r12(**_online_fields(rps=5000.0, online_slo_ms=100.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    paths = [
        _write(tmp_path, "BENCH_r11.json", _r11()),
        _write(tmp_path, "BENCH_r12.json",
               _r12(**_online_fields(rps=5000.0, online_clients=8))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_online_judged_even_on_degraded_newest(tmp_path):
    """Host-side like the other microbenches: a degraded accelerator
    half still measured the real online tier, so its number stays
    gated."""
    paths = [
        _write(tmp_path, "BENCH_r11.json", _r11()),
        _write(tmp_path, "BENCH_r12.json",
               _r12(**_online_fields(rps=5000.0),
                    degraded="accelerator unavailable: probe timeout")),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("online tier regressed" in r for r in verdict["reasons"])


def test_online_breakdown_held_to_reconciliation(tmp_path):
    """The online flight breakdown rides the same reconciliation bar as
    the feed/serving ones: a stage sum that strays >15% from wall fails;
    null + reason (recorder opted out) is exempt."""
    bad = _r11(online_stage_breakdown=_flight_bd(
        frac=0.5, verdict="device_bound"))
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r11.json", bad)])
    assert verdict["verdict"] == "fail"
    assert any("does not reconcile" in r for r in verdict["reasons"])
    opted_out = _r11(online_stage_breakdown=None,
                     online_stage_breakdown_reason="flight recorder "
                                                   "disabled "
                                                   "(TFOS_FLIGHT=0)")
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r11.json", opted_out)])
    assert verdict["verdict"] == "pass", verdict["reasons"]


# -- request-tracing overhead (ISSUE 10) -------------------------------------


def test_trace_overhead_required_on_primary_from_round_12(tmp_path):
    # round 11: grandfathered — no overhead number owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r11.json", _r11())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 12+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r12.json", _r11())])
    assert verdict["verdict"] == "fail"
    assert any("trace_overhead_frac" in r for r in verdict["reasons"])
    # complete round 12 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r12.json", _r12())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (TFOS_TRACE_REQUESTS=0: no A/B)
    half = _r11(trace_overhead_frac=None,
                trace_overhead_reason="request tracing disabled "
                                      "(TFOS_TRACE_REQUESTS=0)")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r12.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r11(trace_overhead_frac=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r12.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("trace_overhead_reason" in r for r in verdict["reasons"])


def test_trace_overhead_must_be_a_fraction(tmp_path):
    """The overhead is 1 - traced/untraced throughput: a value outside
    [-1, 1] is a unit mistake, not a measurement."""
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r12.json",
                _r12(trace_overhead_frac=3.5))])
    assert verdict["verdict"] == "fail"
    assert any("not a fraction" in r for r in verdict["reasons"])
    # judged whenever present, even before round 12
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r11.json",
                _r11(trace_overhead_frac=-2.0))])
    assert verdict["verdict"] == "fail"
    assert any("not a fraction" in r for r in verdict["reasons"])


# -- multi-host serving mesh (ISSUE 11) --------------------------------------


def _mesh_fields(rps=6000.0, p99=40.0, **extra):
    fields = {"mesh_rows_per_sec": rps,
              "mesh_rows_per_sec_single_process": 11000.0,
              "mesh_speedup_vs_single_process": round(rps / 11000.0, 3),
              "mesh_scale_efficiency": round(rps / (3 * 11000.0), 3),
              "mesh_p50_ms": 12.0, "mesh_p99_ms": p99,
              "mesh_p99_ms_single_process": 5.2,
              "mesh_router_hop_ms": 1.4,
              "mesh_replicas": 3, "mesh_clients": 16,
              "mesh_rows_total": 640, "mesh_batch_size": 64,
              "mesh_feature_dim": 256, "mesh_hidden_dim": 1024,
              "mesh_flush_ms": 4.0, "mesh_slo_ms": 500.0,
              "mesh_bucket_sizes": [16, 32, 64],
              "mesh_host_cpus": 1,
              "mesh_trace_linked": True,
              "mesh_kill_lost_requests": 0, "mesh_kill_retries": 12,
              "mesh_kill_loop_seconds": 9.5, "mesh_kill_generation": 1}
    fields.update(extra)
    return fields


def _r13(**extra):
    """A round-13-complete primary half: r12 + the serving mesh."""
    half = _r12(**_mesh_fields())
    half.update(extra)
    return half


def test_mesh_field_required_on_primary_from_round_13(tmp_path):
    # round 12: grandfathered — no mesh number owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r12.json", _r12())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 13+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r13.json", _r12())])
    assert verdict["verdict"] == "fail"
    assert any("mesh_rows_per_sec" in r for r in verdict["reasons"])
    # complete round 13 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r13.json", _r13())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r12(mesh_rows_per_sec=None,
                mesh_reason="wall budget exhausted before serving-mesh "
                            "microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r13.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r12(mesh_rows_per_sec=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r13.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("mesh_reason" in r for r in verdict["reasons"])


def test_mesh_value_without_config_identity_fails(tmp_path):
    half = _r13()
    del half["mesh_host_cpus"]  # N processes vs N cores: part of identity
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r13.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "mesh_host_cpus" in r
               for r in verdict["reasons"])


def test_mesh_value_without_scale_efficiency_fails(tmp_path):
    half = _r13()
    del half["mesh_scale_efficiency"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r13.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("mesh_scale_efficiency" in r for r in verdict["reasons"])


def test_mesh_p99_over_slo_fails(tmp_path):
    half = _r13(mesh_p99_ms=700.0)  # over the 500ms SLO it claims
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r13.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("SLO it missed" in r for r in verdict["reasons"])


def test_mesh_regression_within_geometry_only(tmp_path):
    # same geometry: a halved aggregate rate is a regression (r14+
    # artifacts additionally owe the step-collectives fields — _r14)
    paths = [
        _write(tmp_path, "BENCH_r13.json", _r13()),
        _write(tmp_path, "BENCH_r14.json",
               _r14(**_mesh_fields(rps=2500.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("mesh tier regressed" in r for r in verdict["reasons"])
    # a different host CPU count is a different experiment — no
    # comparison in either direction
    paths = [
        _write(tmp_path, "BENCH_r13.json", _r13()),
        _write(tmp_path, "BENCH_r14.json",
               _r14(**_mesh_fields(rps=2500.0, mesh_host_cpus=8))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_mesh_judged_even_on_degraded_newest(tmp_path):
    """Host-side like the other microbenches: a degraded accelerator
    half still measured the real mesh, so its number stays gated."""
    paths = [
        _write(tmp_path, "BENCH_r13.json", _r13()),
        _write(tmp_path, "BENCH_r14.json",
               _r14(**_mesh_fields(rps=2500.0),
                    degraded="accelerator unavailable: probe timeout")),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("mesh tier regressed" in r for r in verdict["reasons"])


# -- bucketed step collectives (ISSUE 12) ------------------------------------


def _step_fields(rps=58000.0, mono=52000.0, overlap=0.41, **extra):
    fields = {"step_rows_per_sec": rps,
              "step_rows_per_sec_monolithic": mono,
              "allreduce_overlap_frac": overlap,
              "step_output_equality": "pass",
              "step_platform": "cpu", "step_devices": 8,
              "step_model": "mlp_h128x6", "step_batch_size": 512,
              "step_bucket_mb": 0.095, "step_grad_mb": 0.38,
              "step_n_buckets": 6, "step_steps": 8}
    fields.update(extra)
    return fields


def _r14(**extra):
    """A round-14-complete primary half: r13 + the step-collectives A/B."""
    half = _r13(**_step_fields())
    half.update(extra)
    return half


def test_step_field_required_on_primary_from_round_14(tmp_path):
    # round 13: grandfathered — no step A/B owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r13.json", _r13())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 14+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", _r13())])
    assert verdict["verdict"] == "fail"
    assert any("step_rows_per_sec" in r for r in verdict["reasons"])
    # complete round 14 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", _r14())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (this 1-core box: single device,
    # no cross-replica exchange to bucket)
    half = _r13(step_rows_per_sec=None,
                step_reason="single device: no cross-replica gradient "
                            "exchange to bucket or overlap")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r13(step_rows_per_sec=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("step_reason" in r for r in verdict["reasons"])


def test_step_output_equality_failed_fails_artifact(tmp_path):
    """A bucketed step whose losses diverged from the monolithic step is
    broken, not fast — even though it stamps null throughput + reason,
    the artifact must FAIL, not pass as a legitimate null."""
    half = _r13(step_rows_per_sec=None,
                step_output_equality="fail",
                step_reason="bucketed step diverged from the monolithic "
                            "step: throughput not stamped")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("broken, not fast" in r for r in verdict["reasons"])
    # numeric throughput without ANY equality verdict is also unverified
    half = _r14()
    del half["step_output_equality"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("step_output_equality" in r for r in verdict["reasons"])


def test_step_value_without_config_identity_fails(tmp_path):
    half = _r14()
    del half["step_devices"]  # the all-reduce world: part of identity
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "step_devices" in r
               for r in verdict["reasons"])


def test_step_value_without_monolithic_partner_fails(tmp_path):
    half = _r14()
    del half["step_rows_per_sec_monolithic"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("step_rows_per_sec_monolithic" in r
               for r in verdict["reasons"])


def test_step_overlap_frac_range_and_null_reason(tmp_path):
    # overlap outside [-1, 1] is a unit mistake
    verdict = bench_gate.gate(
        [_write(tmp_path, "BENCH_r14.json",
                _r14(allreduce_overlap_frac=3.0))])
    assert verdict["verdict"] == "fail"
    assert any("not a fraction" in r for r in verdict["reasons"])
    # null overlap with a reason is legitimate (ICI unmeasurable) even
    # when the throughput A/B itself is numeric
    half = _r14(allreduce_overlap_frac=None,
                allreduce_overlap_reason="delivered ICI bandwidth "
                                         "unmeasurable: probe dominated "
                                         "by dispatch overhead")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null overlap does not satisfy
    half = _r14(allreduce_overlap_frac=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("allreduce_overlap_reason" in r for r in verdict["reasons"])


def test_step_regression_within_device_count_identity_only(tmp_path):
    # same identity: a halved bucketed throughput is a regression (r15+
    # artifacts additionally owe the compile-cache fields — _r15)
    paths = [
        _write(tmp_path, "BENCH_r14.json", _r14()),
        _write(tmp_path, "BENCH_r15.json",
               _r15(**_step_fields(rps=20000.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("step path regressed" in r for r in verdict["reasons"])
    # a different device count is a different experiment — no comparison
    # in either direction (like mesh_host_cpus in r13)
    paths = [
        _write(tmp_path, "BENCH_r14.json", _r14()),
        _write(tmp_path, "BENCH_r15.json",
               _r15(**_step_fields(rps=20000.0, step_devices=2))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


# -- persistent compile cache cold-start (ISSUE 13) --------------------------


def _coldstart_fields(seconds=1.28, nocache=4.11, **extra):
    fields = {"coldstart_seconds": seconds,
              "coldstart_seconds_nocache": nocache,
              "coldstart_speedup": (round(nocache / seconds, 3)
                                    if seconds and nocache else None),
              "coldstart_disk_hits": 4, "coldstart_disk_writes": 4,
              "coldstart_compiles": 4,
              "coldstart_platform": "cpu", "coldstart_layers": 96,
              "coldstart_width": 256, "coldstart_batch_size": 128,
              "coldstart_buckets": [16, 32, 64, 128],
              "coldstart_host_cpus": 1}
    fields.update(extra)
    return fields


def _r15(**extra):
    """A round-15-complete primary half: r14 + the compile-cache A/B."""
    half = _r14(**_coldstart_fields())
    half.update(extra)
    return half


def test_coldstart_field_required_on_primary_from_round_15(tmp_path):
    # round 14: grandfathered — no cold-start A/B owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r14.json", _r14())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 15+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", _r14())])
    assert verdict["verdict"] == "fail"
    assert any("coldstart_seconds" in r for r in verdict["reasons"])
    # complete round 15 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", _r15())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. ineligible backend)
    half = _r14(coldstart_seconds=None,
                coldstart_reason="seed process wrote no persistent-cache "
                                 "entries: backend cannot serialize "
                                 "executables")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r14(coldstart_seconds=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("coldstart_reason" in r for r in verdict["reasons"])


def test_coldstart_value_without_config_identity_fails(tmp_path):
    half = _r15()
    del half["coldstart_buckets"]  # the ladder: number of warm compiles
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "coldstart_buckets" in r
               for r in verdict["reasons"])


def test_coldstart_value_without_nocache_partner_fails(tmp_path):
    half = _r15()
    del half["coldstart_seconds_nocache"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("coldstart_seconds_nocache" in r
               for r in verdict["reasons"])


def test_coldstart_value_without_disk_hits_fails(tmp_path):
    """A 'cached' arm that took no disk hits measured process overhead,
    not the cache — numeric seconds with zero hits fail the artifact."""
    half = _r15(coldstart_disk_hits=0)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("took no disk hits" in r for r in verdict["reasons"])
    half = _r15()
    del half["coldstart_disk_hits"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", half)])
    assert verdict["verdict"] == "fail"


def test_coldstart_regression_is_lower_is_better(tmp_path):
    # cold start DOUBLED within one config identity: that is the
    # regression this gate exists to catch (a broken cache reads as a
    # slower second process, not an error)
    paths = [
        _write(tmp_path, "BENCH_r15.json", _r15()),
        _write(tmp_path, "BENCH_r16.json",
               _r16(**_coldstart_fields(seconds=2.9))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("cold start slowed" in r for r in verdict["reasons"])
    # and a FASTER cold start passes (lower is better, not different)
    paths = [
        _write(tmp_path, "BENCH_r15.json", _r15()),
        _write(tmp_path, "BENCH_r16.json",
               _r16(**_coldstart_fields(seconds=0.9))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_coldstart_not_compared_across_configs(tmp_path):
    # a different ladder (more warm compiles) is a different experiment
    paths = [
        _write(tmp_path, "BENCH_r15.json", _r15()),
        _write(tmp_path, "BENCH_r16.json",
               _r16(**_coldstart_fields(seconds=2.9,
                                        coldstart_buckets=[128]))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # so is a different host CPU count (XLA compile is CPU-bound)
    paths = [
        _write(tmp_path, "BENCH_r15.json", _r15()),
        _write(tmp_path, "BENCH_r16.json",
               _r16(**_coldstart_fields(seconds=2.9,
                                        coldstart_host_cpus=8))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_coldstart_judged_even_on_degraded_newest(tmp_path):
    """Host-side CPU subprocesses: a degraded accelerator half still
    measured the real cold-start path, so its number stays gated."""
    paths = [
        _write(tmp_path, "BENCH_r15.json", _r15()),
        _write(tmp_path, "BENCH_r16.json",
               _r16(**_coldstart_fields(seconds=2.9),
                    degraded="accelerator unavailable: probe timeout")),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("cold start slowed" in r for r in verdict["reasons"])


# -- generative decode tier (ISSUE 14) ---------------------------------------


def _decode_fields(tps=8000.0, seq=2300.0, ttft_p99=3.2, itl_p99=2.4,
                   **extra):
    fields = {"decode_tokens_per_sec": tps,
              "decode_tokens_per_sec_sequential": seq,
              "decode_speedup": round(tps / seq, 2) if seq else None,
              "decode_output_equality": "pass",
              "decode_tokens_total": 864,
              "decode_ttft_ms_p50": 1.6, "decode_ttft_ms_p99": ttft_p99,
              "decode_itl_ms_p50": 0.4, "decode_itl_ms_p99": itl_p99,
              "decode_ttft_slo_ms": 5000.0, "decode_itl_slo_ms": 1000.0,
              "decode_kv_occupancy_peak": 0.52,
              "decode_clients": 6, "decode_requests": 36,
              "decode_max_new_tokens": 24,
              "decode_prompt_lens": [8, 24],
              "decode_model": "tiny_lm_d32L2H2v64",
              "decode_page_size": 8, "decode_max_seqs": 8,
              "decode_num_pages": 65,
              "decode_prefill_buckets": [8, 16, 32],
              "decode_devices": 1, "decode_host_cpus": 1,
              "decode_stage_breakdown": _flight_bd(
                  verdict="decode_bound",
                  stages_s={"wait": 1.0, "prefill": 2.0, "decode": 7.0})}
    fields.update(extra)
    return fields


def _r16(**extra):
    """A round-16-complete primary half: r15 + the generative-decode
    A/B."""
    half = _r15(**_decode_fields())
    half.update(extra)
    return half


def test_decode_field_required_on_primary_from_round_16(tmp_path):
    # round 15: grandfathered — no decode A/B owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r15.json", _r15())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 16+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", _r15())])
    assert verdict["verdict"] == "fail"
    assert any("decode_tokens_per_sec" in r for r in verdict["reasons"])
    # complete round 16 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", _r16())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r15(decode_tokens_per_sec=None,
                decode_reason="wall budget exhausted before the "
                              "generative decode microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r15(decode_tokens_per_sec=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("decode_reason" in r for r in verdict["reasons"])


def test_decode_output_equality_failed_fails_artifact(tmp_path):
    """Concurrent decode producing different tokens than sequential is
    broken, not fast — even though it stamps null throughput + reason,
    the artifact must FAIL, not pass as a legitimate null."""
    half = _r15(decode_tokens_per_sec=None,
                decode_output_equality="fail",
                decode_reason="3/36 request(s) decoded different tokens "
                              "concurrently vs sequentially")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("broken, not fast" in r for r in verdict["reasons"])
    # numeric throughput without ANY equality verdict is also unverified
    half = _r16()
    del half["decode_output_equality"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("decode_output_equality" in r for r in verdict["reasons"])


def test_decode_value_without_config_identity_fails(tmp_path):
    half = _r16()
    del half["decode_page_size"]  # the paging geometry: part of identity
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "decode_page_size" in r
               for r in verdict["reasons"])


def test_decode_value_without_sequential_partner_fails(tmp_path):
    half = _r16()
    del half["decode_tokens_per_sec_sequential"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("decode_tokens_per_sec_sequential" in r
               for r in verdict["reasons"])


def test_decode_p99_over_slo_fails(tmp_path):
    """A tokens/sec claimed at a TTFT or inter-token SLO the run missed
    is not a measurement — either p99 over its bound fails."""
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r16.json",
        _r16(**_decode_fields(ttft_p99=9000.0)))])
    assert verdict["verdict"] == "fail"
    assert any("decode_ttft_ms_p99" in r and "SLO it missed" in r
               for r in verdict["reasons"])
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r16.json",
        _r16(**_decode_fields(itl_p99=2000.0)))])
    assert verdict["verdict"] == "fail"
    assert any("decode_itl_ms_p99" in r for r in verdict["reasons"])
    # a missing p99 is as bad as a breached one
    half = _r16()
    del half["decode_ttft_ms_p99"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", half)])
    assert verdict["verdict"] == "fail"


def test_decode_throughput_regression_within_identity(tmp_path):
    paths = [
        _write(tmp_path, "BENCH_r16.json", _r16()),
        _write(tmp_path, "BENCH_r17.json",
               _r17(**_decode_fields(tps=3000.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("decode tier regressed" in r for r in verdict["reasons"])
    # a different page size is a different experiment — no comparison
    paths = [
        _write(tmp_path, "BENCH_r16.json", _r16()),
        _write(tmp_path, "BENCH_r17.json",
               _r17(**_decode_fields(tps=3000.0, decode_page_size=16))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_decode_latency_regression_is_lower_is_better(tmp_path):
    # TTFT p99 tripled within one identity while throughput held: the
    # tail regression the latency gates exist to catch
    paths = [
        _write(tmp_path, "BENCH_r16.json", _r16()),
        _write(tmp_path, "BENCH_r17.json",
               _r17(**_decode_fields(ttft_p99=12.0))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("decode tail slowed" in r for r in verdict["reasons"])
    # and a FASTER tail passes (lower is better, not different)
    paths = [
        _write(tmp_path, "BENCH_r16.json", _r16()),
        _write(tmp_path, "BENCH_r17.json",
               _r17(**_decode_fields(ttft_p99=1.1, itl_p99=0.9))),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_decode_judged_even_on_degraded_newest(tmp_path):
    """Host-side like the other serving microbenches: a degraded
    accelerator half still measured the real decode path, so its number
    stays gated."""
    paths = [
        _write(tmp_path, "BENCH_r16.json", _r16()),
        _write(tmp_path, "BENCH_r17.json",
               _r17(**_decode_fields(tps=3000.0),
                    degraded="accelerator unavailable: probe timeout")),
    ]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("decode tier regressed" in r for r in verdict["reasons"])


def test_decode_breakdown_held_to_reconciliation(tmp_path):
    """The decode plane's stage breakdown rides _FLIGHT_BREAKDOWNS: a
    stage sum that does not add up to the wall fails the artifact."""
    bad = _flight_bd(verdict="decode_bound",
                     stages_s={"wait": 1.0, "prefill": 1.0,
                               "decode": 2.0})
    bad["stage_sum_s"] = 4.0
    bad["wall_s"] = 10.0
    bad["stage_sum_frac"] = 0.4
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r16.json",
        _r16(decode_stage_breakdown=bad))])
    assert verdict["verdict"] == "fail"
    # a null breakdown with a reason is exempt (TFOS_FLIGHT=0)
    half = _r16(decode_stage_breakdown=None,
                decode_stage_breakdown_reason="flight recorder disabled "
                                              "(TFOS_FLIGHT=0)")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]


# -- fleet observability plane (ISSUE 15) ------------------------------------


def _fleet_fields(overhead=0.03, detect=1.2, cadence=0.5, **extra):
    fields = {"fleet_overhead_frac": overhead,
              "fleet_router_p99_ms": 22.5,
              "fleet_router_p99_ms_off": 21.8,
              "fleet_skew_detect_s": detect,
              "fleet_skew_replica": "r0",
              "fleet_skew_ratio": 40.0,
              "fleet_skew_rows_per_sec": 210.0,
              "fleet_metrics_valid": True,
              "fleet_scrape_interval_s": cadence,
              "fleet_window_s": 10.0,
              "fleet_ring_depth": 64,
              "fleet_replicas": 2, "fleet_clients": 6,
              "fleet_rows_total": 240, "fleet_host_cpus": 1}
    fields.update(extra)
    return fields


def _r17(**extra):
    """A round-17-complete primary half: r16 + the fleet-observability
    microbench."""
    half = _r16(**_fleet_fields())
    half.update(extra)
    return half


def test_fleet_field_required_on_primary_from_round_17(tmp_path):
    # round 16: grandfathered — no fleet microbench owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r16.json", _r16())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 17+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", _r16())])
    assert verdict["verdict"] == "fail"
    assert any("fleet_overhead_frac" in r for r in verdict["reasons"])
    # complete round 17 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", _r17())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r16(fleet_overhead_frac=None,
                fleet_reason="wall budget exhausted before the fleet-"
                             "observability microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r16(fleet_overhead_frac=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("fleet_reason" in r for r in verdict["reasons"])


def test_fleet_overhead_bound_sanity(tmp_path):
    """The overhead is (p99_on − p99_off)/p99_off: anything outside
    [-1, 1] is a measurement bug, not a measurement."""
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r17.json",
        _r17(**_fleet_fields(overhead=3.7)))])
    assert verdict["verdict"] == "fail"
    assert any("fraction in [-1, 1]" in r for r in verdict["reasons"])
    # a small negative (noise-centered A/B) is legitimate
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r17.json",
        _r17(**_fleet_fields(overhead=-0.02)))])
    assert verdict["verdict"] == "pass", verdict["reasons"]


def test_fleet_string_value_is_rejected_not_skipped(tmp_path):
    """A value that is neither null nor numeric (a JSON string) must
    not slide past the whole r17 block — every fleet requirement hangs
    off the numeric branch."""
    half = _r17(fleet_overhead_frac="0.02")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("must be numeric or an explicit null" in r
               for r in verdict["reasons"])


def test_fleet_value_without_config_identity_fails(tmp_path):
    half = _r17()
    del half["fleet_replicas"]  # the fleet size: part of identity
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "fleet_replicas" in r
               for r in verdict["reasons"])


def test_fleet_skew_detection_bound(tmp_path):
    """The detection claim is gated: a finding later than one cadence
    past the earliest detectable window (2 scrapes bracket the load)
    fails — and a MISSING detection time is as bad as a slow one."""
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r17.json",
        _r17(**_fleet_fields(detect=9.0, cadence=0.5)))])
    assert verdict["verdict"] == "fail"
    assert any("fleet_skew_detect_s" in r and "cadence" in r
               for r in verdict["reasons"])
    half = _r17()
    del half["fleet_skew_detect_s"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("fleet_skew_detect_s" in r for r in verdict["reasons"])


def test_fleet_metrics_must_have_validated(tmp_path):
    half = _r17(fleet_metrics_valid=False)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("fleet_metrics_valid" in r for r in verdict["reasons"])
    half = _r17()
    del half["fleet_metrics_valid"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", half)])
    assert verdict["verdict"] == "fail"


def test_fleet_judged_even_on_degraded_newest(tmp_path):
    """Host-side multi-process like the mesh microbench: a degraded
    accelerator half still ran the real router+collector, so its
    schema stays enforced."""
    half = _r17(**_fleet_fields(overhead=2.5),
                degraded="accelerator unavailable: probe timeout")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("fraction in [-1, 1]" in r for r in verdict["reasons"])


# -- incident plane (ISSUE 16) -----------------------------------------------


def _incident_fields(overhead=0.01, **extra):
    fields = {"incident_overhead_frac": overhead,
              "incident_router_p99_ms": 22.1,
              "incident_router_p99_ms_off": 21.9,
              "incident_timeline_valid": True,
              "incident_death_latency_s": 1.4,
              "incident_journal_events": 87,
              "incident_bundles": 3,
              "incident_linked_traces": 2,
              "incident_replicas": 2, "incident_clients": 6,
              "incident_rows_total": 240, "incident_host_cpus": 1}
    fields.update(extra)
    return fields


def _r18(**extra):
    """A round-18-complete primary half: r17 + the incident-plane
    microbench."""
    half = _r17(**_incident_fields())
    half.update(extra)
    return half


def test_incident_field_required_on_primary_from_round_18(tmp_path):
    # round 17: grandfathered — no incident microbench owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r17.json", _r17())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 18+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", _r17())])
    assert verdict["verdict"] == "fail"
    assert any("incident_overhead_frac" in r for r in verdict["reasons"])
    # complete round 18 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", _r18())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r17(incident_overhead_frac=None,
                incident_reason="wall budget exhausted before the "
                                "incident-plane microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r17(incident_overhead_frac=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("incident_reason" in r for r in verdict["reasons"])


def test_incident_overhead_bound_and_string_rejection(tmp_path):
    """(p99_on − p99_off)/p99_off outside [-1, 1] is a measurement bug;
    a string value must not slide past the whole r18 block."""
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r18.json",
        _r18(**_incident_fields(overhead=2.0)))])
    assert verdict["verdict"] == "fail"
    assert any("fraction in [-1, 1]" in r for r in verdict["reasons"])
    # a small negative (noise-centered A/B — the acceptance claim IS
    # the noise floor) is legitimate
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r18.json",
        _r18(**_incident_fields(overhead=-0.005)))])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    half = _r18(incident_overhead_frac="0.01")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("must be numeric or an explicit null" in r
               for r in verdict["reasons"])


def test_incident_value_without_config_identity_fails(tmp_path):
    half = _r18()
    del half["incident_replicas"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "incident_replicas" in r
               for r in verdict["reasons"])


def test_incident_chaos_proof_gated(tmp_path):
    """The chaos pass is the plane's whole point: an unvalidated
    timeline, a missing death latency, or zero exemplar-linked traces
    each fail the artifact."""
    half = _r18(incident_timeline_valid=False)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("incident_timeline_valid" in r for r in verdict["reasons"])
    half = _r18()
    del half["incident_death_latency_s"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("incident_death_latency_s" in r for r in verdict["reasons"])
    half = _r18(incident_linked_traces=0)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("incident_linked_traces" in r for r in verdict["reasons"])


# -- sharded-update collectives comparison (ISSUE 17) ------------------------


def _collectives_fields(ratio=0.504, **extra):
    fields = {"collectives_bytes_ratio": ratio,
              "collectives_equality": "pass",
              "collectives_rows_per_sec": 41000.0,
              "collectives_rows_per_sec_allreduce": 39000.0,
              "collectives_platform": "cpu", "collectives_devices": 8,
              "collectives_dcn_world": 1,
              "collectives_model": "mlp_h128x6",
              "collectives_grad_mb": 0.3799,
              "collectives_bucket_mb": 0.095,
              "collectives_update_shard": True}
    fields.update(extra)
    return fields


def _r19(**extra):
    """A round-19-complete primary half: r18 + the sharded-update
    collectives comparison."""
    half = _r18(**_collectives_fields())
    half.update(extra)
    return half


def test_collectives_field_required_on_primary_from_round_19(tmp_path):
    # round 18: grandfathered — no collectives comparison owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r18.json", _r18())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 19+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", _r18())])
    assert verdict["verdict"] == "fail"
    assert any("collectives_bytes_ratio" in r for r in verdict["reasons"])
    # complete round 19 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", _r19())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r18(collectives_bytes_ratio=None,
                collectives_reason="wall budget exhausted before "
                                   "collectives microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r18(collectives_bytes_ratio=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("collectives_reason" in r for r in verdict["reasons"])


def test_collectives_single_device_shape_passes(tmp_path):
    # the 1-device headline box: analytic ratio numeric, equality and
    # throughput null with the shared reason — a complete, honest half
    half = _r19(collectives_equality=None,
                collectives_rows_per_sec=None,
                collectives_rows_per_sec_allreduce=None,
                collectives_reason="single device: wall-clock deferred "
                                   "to hardware")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # but a numeric ratio with a bare null equality (no reason) fails —
    # the half must say why the A/B could not run
    half = _r19(collectives_equality=None, collectives_rows_per_sec=None,
                collectives_rows_per_sec_allreduce=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("collectives_equality" in r for r in verdict["reasons"])


def test_collectives_equality_fail_fails_artifact(tmp_path):
    """A diverged sharded-update step is broken, not fast — it fails the
    artifact even though it also stamps a legitimate-looking null
    throughput + reason."""
    half = _r19(collectives_equality="fail",
                collectives_rows_per_sec=None,
                collectives_rows_per_sec_allreduce=None,
                collectives_reason="sharded-update step diverged from "
                                   "the bucketed all-reduce step")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("broken, not fast" in r for r in verdict["reasons"])


def test_collectives_ratio_bound_and_string_rejection(tmp_path):
    """A ratio at or above 1 means the restructured exchange moves no
    fewer bytes — not an optimization; a string value must not slide
    past the whole r19 block."""
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r19.json",
        _r19(**_collectives_fields(ratio=1.2)))])
    assert verdict["verdict"] == "fail"
    assert any("not strictly inside (0, 1)" in r
               for r in verdict["reasons"])
    half = _r19(collectives_bytes_ratio="0.5")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("must be numeric or an explicit null" in r
               for r in verdict["reasons"])


def test_collectives_value_without_config_identity_fails(tmp_path):
    half = _r19()
    del half["collectives_devices"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "collectives_devices" in r
               for r in verdict["reasons"])


def test_collectives_throughput_needs_its_ab_partner(tmp_path):
    half = _r19()
    del half["collectives_rows_per_sec_allreduce"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("collectives_rows_per_sec_allreduce" in r
               for r in verdict["reasons"])


def test_collectives_ratio_regression_within_identity_only(tmp_path):
    # same config, worse (higher) ratio beyond 1/threshold: fail
    # (round-20 artifacts must be r20-complete — the costs microbench is
    # owed there — so the comparison rides _r20 halves)
    paths = [
        _write(tmp_path, "BENCH_r19.json", _r19()),
        _write(tmp_path, "BENCH_r20.json",
               _r20(**_collectives_fields(ratio=0.71)))]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("moves more bytes" in r for r in verdict["reasons"])
    # a different device count is a different experiment: no comparison
    paths = [
        _write(tmp_path, "BENCH_r19.json", _r19()),
        _write(tmp_path, "BENCH_r20.json",
               _r20(**_collectives_fields(ratio=0.71,
                                          collectives_devices=16)))]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]


# -- per-tenant cost accounting + goodput ledger (ISSUE 18) ------------------


def _costs_fields(ratio=1.0, **extra):
    fields = {"costs_conservation_ratio": ratio,
              "costs_flight_ratio": 1.0,
              "costs_overhead_frac": -0.02,
              "costs_p99_ms": 9.9, "costs_p99_ms_off": 9.7,
              "costs_skew_detect_s": 1.01,
              "costs_skew_tenant": "t0", "costs_skew_share": 0.85,
              "costs_goodput_breakdown": {
                  "wall_s": 0.48, "stage_sum_s": 0.47,
                  "stage_sum_frac": 0.979,
                  "phases_s": {"productive": 0.01, "input_wait": 0.02,
                               "compile": 0.39, "checkpoint": 0.04,
                               "recovery": 0.0, "stall": 0.01},
                  "productive_frac": 0.021, "steps": 10},
              "costs_goodput_productive_frac": 0.021,
              "costs_tenants": 3, "costs_clients": 6,
              "costs_rows_total": 150, "costs_cadence_s": 1.0,
              "costs_host_cpus": 1}
    fields.update(extra)
    return fields


def _r20(**extra):
    """A round-20-complete primary half: r19 + the cost-accounting
    microbench."""
    half = _r19(**_costs_fields())
    half.update(extra)
    return half


def test_costs_field_required_on_primary_from_round_20(tmp_path):
    # round 19: grandfathered — no cost-accounting microbench owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r19.json", _r19())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 20+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", _r19())])
    assert verdict["verdict"] == "fail"
    assert any("costs_conservation_ratio" in r for r in verdict["reasons"])
    # complete round 20 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", _r20())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r19(costs_conservation_ratio=None,
                costs_reason="wall budget exhausted before cost "
                             "microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r19(costs_conservation_ratio=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("costs_reason" in r for r in verdict["reasons"])


def test_costs_conservation_drift_and_string_rejection(tmp_path):
    """Charges that do not re-add to the engine seconds they were carved
    from fail the artifact; a string must not slide past the block."""
    verdict = bench_gate.gate([_write(
        tmp_path, "BENCH_r20.json", _r20(**_costs_fields(ratio=1.05)))])
    assert verdict["verdict"] == "fail"
    assert any("drifts more than 1%" in r for r in verdict["reasons"])
    half = _r20(costs_conservation_ratio="1.0")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("must be numeric or an explicit null" in r
               for r in verdict["reasons"])


def test_costs_value_without_config_identity_fails(tmp_path):
    half = _r20()
    del half["costs_clients"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "costs_clients" in r
               for r in verdict["reasons"])


def test_costs_overhead_must_ride_the_ratio(tmp_path):
    half = _r20(costs_overhead_frac=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("costs_overhead_frac" in r for r in verdict["reasons"])


def test_costs_skew_detection_inside_judged_budget(tmp_path):
    # a detection latency past 3x cadence + 1s is an autopsy
    half = _r20(costs_skew_detect_s=10.0)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("autopsy" in r for r in verdict["reasons"])
    # a never-caught dominant tenant cannot back the stamped ratio
    half = _r20(costs_skew_detect_s=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("never caught" in r for r in verdict["reasons"])


def test_costs_goodput_breakdown_must_reconcile(tmp_path):
    bd = dict(_costs_fields()["costs_goodput_breakdown"])
    bd["stage_sum_s"] = 0.10  # 0.208 of the 0.48 wall: phases missing
    half = _r20(costs_goodput_breakdown=bd)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("does not reconcile" in r for r in verdict["reasons"])
    # no breakdown at all: the goodput ledger is part of the claim
    half = _r20(costs_goodput_breakdown=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("costs_goodput_breakdown" in r for r in verdict["reasons"])


# -- chunked prefill + prefix sharing (ISSUE 19) -----------------------------


def _prefill_fields(ttft=0.8, **extra):
    fields = {"decode_prefill_short_ttft_ms_p99": ttft,
              "decode_prefill_output_equality": "pass",
              "decode_prefill_alloc_pages": 34,
              "decode_prefill_alloc_pages_baseline": 60,
              "decode_prefill_page_savings_frac": 0.4333,
              "decode_prefill_short_ttft_speedup": None,
              "decode_prefill_short_ttft_speedup_reason":
                  "compute-bound single-device host: packed prefill "
                  "costs more FLOPs than per-prompt calls",
              "decode_prefill_clients": 6,
              "decode_prefill_requests": 24,
              "decode_prefill_shared_requests": 6,
              "decode_prefill_max_new_tokens": 8,
              "decode_prefill_prompt_lens": [4, 20],
              "decode_prefill_prefix_len": 16,
              "decode_prefill_chunk": 8,
              "decode_prefill_chunks": [8, 16, 24],
              "decode_prefill_model": "tiny_lm_d32L2H2v64",
              "decode_prefill_page_size": 8,
              "decode_prefill_max_seqs": 8,
              "decode_prefill_devices": 1,
              "decode_prefill_host_cpus": 1}
    fields.update(extra)
    return fields


def _r21(**extra):
    """A round-21-complete primary half: r20 + the chunked-prefill +
    prefix-sharing microbench."""
    half = _r20(**_prefill_fields())
    half.update(extra)
    return half


def test_decode_prefill_field_required_on_primary_from_round_21(tmp_path):
    # round 20: grandfathered — no chunked-prefill microbench owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r20.json", _r20())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 21+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r21.json", _r20())])
    assert verdict["verdict"] == "fail"
    assert any("decode_prefill_short_ttft_ms_p99" in r
               for r in verdict["reasons"])
    # complete round 21 passes
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r21.json", _r21())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r20(decode_prefill_short_ttft_ms_p99=None,
                decode_prefill_reason="wall budget exhausted before the "
                                      "chunked-prefill microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r21.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r20(decode_prefill_short_ttft_ms_p99=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r21.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("decode_prefill_reason" in r for r in verdict["reasons"])


def test_decode_prefill_equality_fail_fails_artifact(tmp_path):
    """A diverged chunked+shared prefill is broken, not fast — it fails
    the artifact even though it also stamps a legitimate-looking null
    headline + reason."""
    half = _r20(**_prefill_fields(
        ttft=None, decode_prefill_output_equality="fail",
        decode_prefill_reason="3 request(s) decoded different tokens "
                              "chunked vs per-prompt: broken, not fast"))
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r21.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("broken, not fast" in r for r in verdict["reasons"])


# -- speculative multi-token decoding + seeded sampling (ISSUE 20) -----------


def _spec_fields(ratio=1.32, **extra):
    # mirrors the shape the bench stamps on a compute-bound 1-core host:
    # ITL ratio numeric, speedup null + reason, mechanism evidence
    # (tokens/step, acceptance) numeric, equality verified
    fields = {"spec_itl_p99_ratio": ratio,
              "decode_spec_output_equality": "pass",
              "spec_tokens_per_step": 7.24,
              "spec_acceptance_rate": 0.9366,
              "spec_itl_speedup": None,
              "spec_itl_speedup_reason":
                  "compute-bound single-device host: the (k+1)-position "
                  "verify call costs more FLOPs than the steps it "
                  "collapses",
              "spec_clients": 6, "spec_requests": 24,
              "spec_shared_requests": 6, "spec_max_new_tokens": 24,
              "spec_prompt_lens": [4, 20], "spec_prefix_len": 16,
              "spec_k": 4, "spec_drafter": "ngram",
              "spec_ladder": [1, 2, 4],
              "spec_model": "tiny_lm_d32L2H2v64",
              "spec_page_size": 8, "spec_max_seqs": 8,
              "spec_prefill_chunk": 8, "spec_devices": 1,
              "spec_host_cpus": 1}
    fields.update(extra)
    return fields


def _r22(**extra):
    """A round-22-complete primary half: r21 + the speculative-decoding
    microbench."""
    half = _r21(**_spec_fields())
    half.update(extra)
    return half


def test_decode_spec_field_required_on_primary_from_round_22(tmp_path):
    # round 21: grandfathered — no speculative microbench owed
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r21.json", _r21())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # round 22+: the primary must carry it (or explicit null + reason)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", _r21())])
    assert verdict["verdict"] == "fail"
    assert any("spec_itl_p99_ratio" in r for r in verdict["reasons"])
    # complete round 22 passes (speedup null + reason: the compute-bound
    # host shape — the equality and tokens-per-step claims still gate)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", _r22())])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # explicit null + reason satisfies (e.g. wall budget exhausted)
    half = _r21(spec_itl_p99_ratio=None,
                spec_reason="wall budget exhausted before the "
                            "speculative-decode microbench")
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # bare null does not
    half = _r21(spec_itl_p99_ratio=None)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("spec_reason" in r for r in verdict["reasons"])


def test_decode_spec_equality_fail_fails_artifact(tmp_path):
    """A speculative stream that diverged from the single-token engine is
    broken, not fast — it fails the artifact even though it also stamps a
    legitimate-looking null headline + reason."""
    half = _r21(**_spec_fields(
        ratio=None, decode_spec_output_equality="fail",
        spec_reason="2 request(s) decoded different tokens speculative "
                    "vs single-token: broken, not fast"))
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("broken, not fast" in r for r in verdict["reasons"])


def test_decode_spec_numeric_requires_mechanism_evidence(tmp_path):
    # tokens/step at 1.0 means no draft was ever accepted: the ratio
    # measured a plain decode loop wearing a speculation costume
    half = _r22(spec_tokens_per_step=1.0)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("spec_tokens_per_step" in r for r in verdict["reasons"])
    # an acceptance rate outside [0, 1] (or missing) is not a rate
    half = _r22(spec_acceptance_rate=1.4)
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("spec_acceptance_rate" in r for r in verdict["reasons"])
    half = _r22()
    del half["spec_acceptance_rate"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("spec_acceptance_rate" in r for r in verdict["reasons"])
    # a null speedup must say why (compute-bound host, SLO, ...)
    half = _r22()
    del half["spec_itl_speedup_reason"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("spec_itl_speedup_reason" in r for r in verdict["reasons"])
    # equality must be the verified 'pass', not absent
    half = _r22()
    del half["decode_spec_output_equality"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("decode_spec_output_equality" in r
               for r in verdict["reasons"])


def test_decode_spec_value_without_config_identity_fails(tmp_path):
    half = _r22()
    del half["spec_drafter"]
    del half["spec_k"]
    verdict = bench_gate.gate([_write(tmp_path, "BENCH_r22.json", half)])
    assert verdict["verdict"] == "fail"
    assert any("config identity" in r and "spec_drafter" in r
               and "spec_k" in r for r in verdict["reasons"])


def test_decode_spec_itl_ratio_ratchets_lower_is_better(tmp_path):
    # same config, higher (worse) ratio beyond 1/threshold: fail
    paths = [
        _write(tmp_path, "BENCH_r22.json", _r22()),
        _write(tmp_path, "BENCH_r23.json",
               _r22(**_spec_fields(ratio=1.9)))]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "fail"
    assert any("slowed" in r and "spec_itl_p99_ratio" in r
               for r in verdict["reasons"])
    # a lower (better) ratio passes
    paths = [
        _write(tmp_path, "BENCH_r22.json", _r22()),
        _write(tmp_path, "BENCH_r23.json",
               _r22(**_spec_fields(ratio=1.1)))]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
    # a different drafter or draft depth is a different experiment:
    # no comparison
    paths = [
        _write(tmp_path, "BENCH_r22.json", _r22()),
        _write(tmp_path, "BENCH_r23.json",
               _r22(**_spec_fields(ratio=1.9, spec_k=6,
                                   spec_ladder=[1, 3, 6])))]
    verdict = bench_gate.gate(paths)
    assert verdict["verdict"] == "pass", verdict["reasons"]
