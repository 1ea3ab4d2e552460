#!/usr/bin/env python
"""Bench regression gate: judge the newest BENCH artifact against history.

Reads the ``BENCH_r*.json`` trajectory (the per-round wrappers the driver
writes: ``{"n", "cmd", "rc", "tail", "parsed"}``), schema-validates every
artifact, and compares the newest run's numbers against (a) the self-set
targets already baked into each artifact's ``vs_baseline`` and (b) the best
prior *comparable* run — same metric, same platform, non-degraded, timing
not suspect.  Emits ONE machine-readable verdict JSON line:

- ``"verdict": "pass"`` — newest run is healthy and within ``--threshold``
  of the best prior comparable number;
- ``"verdict": "skip"`` — newest run is loudly degraded (CPU fallback with
  a ``degraded`` stamp): its numbers are not performance evidence, so no
  regression judgment is possible — but the artifact itself validated;
- ``"verdict": "fail"`` — a perf regression, a target-floor breach, a
  malformed artifact, or a **silently** degraded newest artifact
  (``parsed: null`` — the round-4 failure mode: a wedged run that left no
  number and no explanation).

Prior-round empty artifacts are recorded as ``warn`` checks, not failures —
they are history, already explained in BENCH_NOTES.md; only the *newest*
run must stand on its own.  From round ``--require-roofline-from`` (default
6, the round that introduced in-run roofline probes) every half must also
carry ``mem_bw_gbps``/``ici_bw_gbps`` (explicit ``null`` + reason allowed)
so the artifact schema stays total.  From round ``--require-feed-from``
(default 7, the round that introduced the zero-copy data plane) the primary
half must carry ``feed_rows_per_sec`` with its ``feed_transport``
attribution (again: explicit ``null`` + ``feed_transport_reason`` allowed);
a healthy feed number is regression-judged against the best prior run with
the same transport and feed config.  From round ``--require-serving-from``
(default 8, the round that introduced the bucketed serving data plane) the
primary half must likewise carry ``serve_rows_per_sec`` with its
``serve_ingest`` attribution (or explicit ``null`` + ``serve_reason``);
healthy serving numbers are only compared across runs with the same ingest
representation and bucket geometry.  From round ``--require-flight-from``
(default 9, the round that introduced the pipeline flight recorder) every
healthy feed/serving number must also ship its stage-time breakdown
(``feed_stage_breakdown`` / ``serve_stage_breakdown``) with a bottleneck
verdict, and the breakdown's additive stage sum must reconcile with the
measured wall time within ``--flight-tolerance`` (default 0.15) — a
decomposition that does not add up fails the artifact.  From round
``--require-recovery-from`` (default 10, the round that introduced elastic
membership) the primary half must carry ``recovery_seconds`` (SIGKILL →
first post-restore step; explicit ``null`` + ``recovery_reason`` allowed);
recovery is a latency, so a healthy number is regression-judged LOWER-is-
better against the best (minimum) prior run with the same cluster /
checkpoint-cadence / kill config.  From round ``--require-online-from``
(default 11, the round that introduced the continuous-batching online
serving tier) the primary half must carry ``online_rows_per_sec`` with its
p99-bound config identity — a closed-loop throughput is only meaningful AT
its measured p99, so a numeric value must ship ``online_p99_ms`` within
``online_slo_ms`` (or explicit ``null`` + ``online_reason``); healthy
numbers are only compared across runs with the same client count, model
geometry, bucket ladder and SLO.  From round ``--require-trace-from``
(default 12, the round that introduced request-scoped tracing) the primary
half must carry ``trace_overhead_frac`` — the A/B-measured cost of
request tracing on the online path (enabled vs ``TFOS_TRACE_REQUESTS=0``)
— as a fraction in [-1, 1], or an explicit ``null`` +
``trace_overhead_reason`` (same convention as the flight breakdowns).
From round ``--require-mesh-from`` (default 13, the round that introduced
the multi-host serving mesh) the primary half must carry
``mesh_rows_per_sec`` — aggregate closed-loop throughput of N replica
processes behind the placement router — or an explicit ``null`` +
``mesh_reason``; a numeric value must ship its config identity
(replica/client/geometry/SLO *and host CPU count*: N processes cannot
scale past the cores the box has, so scale efficiency is only comparable
at one CPU count), its ``mesh_scale_efficiency`` (mesh ÷ replicas ×
single-process baseline), and a ``mesh_p99_ms`` within ``mesh_slo_ms``;
healthy numbers are regression-compared only within one mesh geometry.
From round ``--require-step-from`` (default 14, the round that introduced
bucketed, overlapped gradient collectives on the train-step path) the
primary half must carry ``step_rows_per_sec`` — the bucketed step's
closed-loop training throughput, A/B'd against the monolithic step in the
same run — or an explicit ``null`` + ``step_reason`` (a single-device box
has no cross-replica exchange to bucket); a numeric value must ship its
``step_rows_per_sec_monolithic`` partner, its config identity (platform,
device count, model, batch, bucket_mb: a different device count is a
different experiment, like ``mesh_host_cpus`` in r13), a
``step_output_equality`` of ``"pass"`` (a bucketed step whose losses
diverged from the monolithic step is broken, not fast — the artifact
FAILS), and ``allreduce_overlap_frac`` as a fraction in [-1, 1] (or
explicit ``null`` + ``allreduce_overlap_reason`` when the delivered ICI
bandwidth is unmeasurable); healthy numbers are regression-compared only
within one step config identity.  From round ``--require-coldstart-from``
(default 15, the round that introduced the persistent compile cache) the
primary half must carry ``coldstart_seconds`` — second-process cold
start (fresh subprocess, real tenant load + ladder warmup, time to first
served request) measured against a seeded compile-cache directory —
or an explicit ``null`` + ``coldstart_reason``; a numeric value must
ship its cache-off A/B partner ``coldstart_seconds_nocache``, a numeric
``coldstart_disk_hits`` (a "cached" arm that never touched disk measured
nothing), and its config identity (platform, model geometry, bucket
ladder, host CPU count); cold start is a latency, so healthy numbers are
regression-judged LOWER-is-better within one config identity, like
``recovery_seconds``.  From round ``--require-decode-from`` (default 16,
the round that introduced token-level continuous batching for generative
decode) the primary half must carry ``decode_tokens_per_sec`` — the
continuous-batching engine's closed-loop aggregate token throughput over
the paged KV pool, A/B'd against sequential per-request decode in the
same run — or an explicit ``null`` + ``decode_reason``; a numeric value
must ship its ``decode_tokens_per_sec_sequential`` partner, a
``decode_output_equality`` of ``"pass"`` (token-level divergence between
concurrent and sequential decode FAILS the artifact — broken, not fast),
its config identity (model geometry, page size, slot count, ladder,
SLOs, device and host-CPU counts), and both latency p99s
(``decode_ttft_ms_p99`` / ``decode_itl_ms_p99``) at or under their SLOs;
the throughput is regression-judged higher-is-better and the two latency
p99s LOWER-is-better, all within one decode config identity.  From round
``--require-fleet-from`` (default 17, the round that introduced the fleet
observability plane) the primary half must carry ``fleet_overhead_frac``
— the A/B-measured router-p99 cost of the fleet collector (scrape+judge
on vs off) — as a fraction in [-1, 1], or an explicit ``null`` +
``fleet_reason``; a numeric value must ship its config identity (replica
and client counts, request volume, scrape cadence, host CPU count — the
scrape thread competes with routing for cores), a numeric
``fleet_skew_detect_s`` at or under ``3 × fleet_scrape_interval_s + 1``
(two cadences bracket the induced hot-replica window, one further
cadence fires the ``fleet.load_skew`` finding; the 1s is subprocess
slack), and ``fleet_metrics_valid`` true (the federated
``/fleet/metrics`` exposition schema-validated in-run).  From round
``--require-incident-from`` (default 18, the round that introduced the
incident plane) the primary half must carry ``incident_overhead_frac``
— the A/B-measured router-p99 cost of the event journal (on vs off) —
as a fraction in [-1, 1], or an explicit ``null`` +
``incident_reason``; a numeric value must ship its config identity
(replica/client counts, request volume, host CPU count),
``incident_timeline_valid`` true (the in-run SIGKILL chaos pass: one
causally-ordered timeline spanning router and corpse, with the death
event, the generation-fenced regroup, and ≥ 1 exemplar-linked
recovered trace — reconstructed by ``tools/incident.py``), a numeric
``incident_death_latency_s``, and ``incident_linked_traces`` ≥ 1.

From round ``--require-collectives-from`` (default 19, the round that
introduced the reduce-scatter bucketed exchange with sharded optimizer
updates) the primary half must carry ``collectives_bytes_ratio`` — the
analytic gradient-EXCHANGE bytes of the scatter path over the all-reduce
path for the toy model's parameter tree — or an explicit ``null`` +
``collectives_reason``.  A numeric ratio must be strictly inside (0, 1):
a scattered exchange that moves as many bytes as the all-reduce it
replaced is not an optimization, and the ratio is the claim the gate
ratchets (LOWER is better) within one config identity (platform, device
count, DCN world, model geometry, gradient/bucket sizing, update-shard
mode).  ``collectives_equality`` of ``"fail"`` FAILS the artifact
outright — a sharded-update step whose losses diverged from the
all-reduce step's is broken, not fast — and a numeric
``collectives_rows_per_sec`` requires both a PASSING equality check and
its ``collectives_rows_per_sec_allreduce`` A/B partner from the same
run; on a single-device box equality and throughput are an explicit
``null`` + ``collectives_reason`` while the analytic ratio stays
numeric.

From round ``--require-costs-from`` (default 20, the round that
introduced the per-tenant cost ledger and training goodput breakdown)
the primary half must carry ``costs_conservation_ratio`` — apportioned
per-tenant device-seconds plus padding waste over the engine seconds
they were split from — or an explicit ``null`` + ``costs_reason``.  A
numeric ratio must sit within 1% of 1.0 (charges that do not re-add to
the walls they were carved from make every chargeback line fiction),
carry its config identity (tenant/client counts, request volume,
judgment cadence, host CPUs), an A/B-measured ``costs_overhead_frac``
in [-1, 1], a ``costs_skew_detect_s`` within the judged budget of
3 x cadence + 1 s (an induced dominant tenant must be caught by
``fleet.cost_skew`` while it is still dominant), and a
``costs_goodput_breakdown`` whose phase sum reconciles to the measured
training wall within the flight tolerance.

From round ``--require-decode-prefill-from`` (default 21, the round
that introduced chunked batched prefill + copy-on-write prefix sharing
on the paged decode tier) the primary half must carry
``decode_prefill_short_ttft_ms_p99`` — the short-prompt time-to-first-
token p99 under a mixed short/long + shared-prefix workload on the
chunked engine — or an explicit ``null`` + ``decode_prefill_reason``.
``decode_prefill_output_equality`` of ``"fail"`` FAILS the artifact
outright — a chunked prefill whose decoded tokens diverged from the
per-prompt engine's is broken, not fast.  A numeric p99 must carry its
config identity (prompt mix, shared-prefix length/volume, chunk
ladder, page/slot geometry, model, device/CPU counts), a PASSING
equality check, and the page-allocation A/B
(``decode_prefill_alloc_pages`` vs ``..._baseline`` plus
``decode_prefill_page_savings_frac`` — the sub-linear unique-pages
claim); the TTFT p99 is regression-gated LOWER-is-better within that
identity.  ``decode_prefill_short_ttft_speedup`` may be ``null`` only
with a ``decode_prefill_short_ttft_speedup_reason`` — a compute-bound
single-device host pays real FLOPs for the packed fixed-shape prefill
geometry that a dispatch-bound accelerator gets for ~one slot's
dispatch cost, so the TTFT claim is not measurable there while the
sharing and equality claims still are.

Usage::

    python tools/bench_gate.py                  # repo-root BENCH_r*.json
    python tools/bench_gate.py --repo /path     # another trajectory dir
    python tools/bench_gate.py A.json B.json    # explicit artifact list

Exit code 0 on pass/skip, 1 on fail, 2 on usage error.  Wired into tier-1
via ``tests/test_bench_gate.py`` (in-tree trajectory must gate clean).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any

#: newest/next-vs-best-prior ratio below which a number is a regression
DEFAULT_THRESHOLD = 0.85
#: minimum vs_baseline (value / self-set target) a healthy run must clear
DEFAULT_TARGET_FLOOR = 0.25
#: first round whose artifacts must carry the roofline fields
DEFAULT_REQUIRE_ROOFLINE_FROM = 6
#: first round whose primary half must carry the feed-transport microbench
#: (``feed_rows_per_sec``, introduced with the zero-copy data plane)
DEFAULT_REQUIRE_FEED_FROM = 7
#: first round whose primary half must carry the serving microbench
#: (``serve_rows_per_sec``, introduced with the bucketed serving data plane)
DEFAULT_REQUIRE_SERVING_FROM = 8
#: first round whose feed/serving numbers must each ship a flight-recorder
#: stage breakdown that reconciles with measured wall time
DEFAULT_REQUIRE_FLIGHT_FROM = 9
#: first round whose primary half must carry the elastic recovery-time
#: microbench (``recovery_seconds``, introduced with elastic membership)
DEFAULT_REQUIRE_RECOVERY_FROM = 10
#: first round whose primary half must carry the online-serving microbench
#: (``online_rows_per_sec``, introduced with the continuous-batching tier)
DEFAULT_REQUIRE_ONLINE_FROM = 11
#: first round whose primary half must carry the measured request-tracing
#: overhead (``trace_overhead_frac``, introduced with request-scoped
#: distributed tracing)
DEFAULT_REQUIRE_TRACE_FROM = 12
#: first round whose primary half must carry the serving-mesh microbench
#: (``mesh_rows_per_sec``, introduced with the multi-host serving mesh)
DEFAULT_REQUIRE_MESH_FROM = 13
#: first round whose primary half must carry the step-collectives A/B
#: (``step_rows_per_sec``, introduced with bucketed, overlapped gradient
#: collectives on the train-step path)
DEFAULT_REQUIRE_STEP_FROM = 14
#: first round whose primary half must carry the compile-cache cold-start
#: A/B (``coldstart_seconds``, introduced with the persistent compile
#: cache + shape-policy unification)
DEFAULT_REQUIRE_COLDSTART_FROM = 15
#: first round whose primary half must carry the generative-decode A/B
#: (``decode_tokens_per_sec``, introduced with token-level continuous
#: batching over the paged KV-cache pool)
DEFAULT_REQUIRE_DECODE_FROM = 16
#: first round whose primary half must carry the fleet-observability
#: microbench (``fleet_overhead_frac``, introduced with the federated
#: metrics / SLO burn-rate / load-skew plane on the mesh router)
DEFAULT_REQUIRE_FLEET_FROM = 17
#: first round whose primary half must carry the incident-plane
#: microbench (``incident_overhead_frac``, introduced with the
#: causally-ordered event journal + black-box dumps + tail forensics)
DEFAULT_REQUIRE_INCIDENT_FROM = 18
#: first round whose primary half must carry the sharded-weight-update
#: collectives comparison (``collectives_bytes_ratio``, introduced with
#: the reduce-scatter bucketed exchange + sharded optimizer updates)
DEFAULT_REQUIRE_COLLECTIVES_FROM = 19
#: first round whose primary half must carry the cost-accounting
#: microbench (``costs_conservation_ratio``, introduced with the
#: per-tenant cost ledger + training goodput breakdown)
DEFAULT_REQUIRE_COSTS_FROM = 20
#: first round whose primary half must carry the chunked-prefill +
#: prefix-sharing microbench (``decode_prefill_short_ttft_ms_p99``,
#: introduced with chunked batched prefill + COW prefix sharing on the
#: paged decode tier)
DEFAULT_REQUIRE_DECODE_PREFILL_FROM = 21
#: first round whose primary half must carry the speculative-decoding
#: microbench (``spec_itl_p99_ratio``, introduced with drafted
#: multi-token verification + seeded real sampling on the paged decode
#: tier)
DEFAULT_REQUIRE_DECODE_SPEC_FROM = 22
#: |stage_sum / wall - 1| beyond this fails the artifact: a breakdown that
#: does not add up is decoration, not attribution
DEFAULT_FLIGHT_TOLERANCE = 0.15

_REQUIRED_HALF_KEYS = ("metric", "value", "unit", "vs_baseline")
_ROOFLINE_KEYS = ("mem_bw_gbps", "ici_bw_gbps")
_FEED_KEY = "feed_rows_per_sec"
_SERVE_KEY = "serve_rows_per_sec"
_RECOVERY_KEY = "recovery_seconds"
#: the recovery microbench's config identity: SIGKILL→first-step seconds
#: are only comparable across runs with the same cluster size, checkpoint
#: cadence, and kill point — a different cadence bounds a different
#: amount of lost work
_RECOVERY_IDENT_KEYS = ("recovery_num_executors",
                        "recovery_ckpt_every_steps",
                        "recovery_kill_at_step", "recovery_batch_size")
_ONLINE_KEY = "online_rows_per_sec"
_TRACE_OVERHEAD_KEY = "trace_overhead_frac"
_MESH_KEY = "mesh_rows_per_sec"
_STEP_KEY = "step_rows_per_sec"
_COLDSTART_KEY = "coldstart_seconds"
#: the compile-cache cold-start's config identity: seconds to first
#: served request are only comparable at the same platform, model
#: geometry (compile cost), bucket ladder (number of warm compiles) and
#: host CPU count (XLA compile is CPU-bound)
_COLDSTART_IDENT_KEYS = ("coldstart_platform", "coldstart_layers",
                         "coldstart_width", "coldstart_batch_size",
                         "coldstart_buckets", "coldstart_host_cpus")
#: the step-collectives A/B's config identity: bucketed-step rows/sec is
#: only comparable at the same platform, DEVICE COUNT (the all-reduce
#: world — a number with no interconnect to hide is a different
#: experiment), model geometry, global batch and bucket size
_STEP_IDENT_KEYS = ("step_platform", "step_devices", "step_model",
                    "step_batch_size", "step_bucket_mb")
#: the mesh microbench's config identity: aggregate rows/sec is only
#: comparable at the same replica/client counts, request volume, model
#: geometry, bucket ladder, SLO AND host CPU count — N processes cannot
#: scale past the cores the box has, so a number measured on a different
#: core count is a different experiment
_MESH_IDENT_KEYS = ("mesh_replicas", "mesh_clients", "mesh_rows_total",
                    "mesh_batch_size", "mesh_feature_dim",
                    "mesh_hidden_dim", "mesh_bucket_sizes",
                    "mesh_slo_ms", "mesh_flush_ms", "mesh_host_cpus")
#: the online microbench's config identity: closed-loop rows/sec is only
#: comparable at the same client count / request volume / model geometry /
#: bucket ladder AND the same p99 SLO — a number sustained at a looser
#: SLO is a different experiment (that is the whole point of quoting
#: throughput AT an SLO)
_ONLINE_IDENT_KEYS = ("online_clients", "online_rows_total",
                      "online_batch_size", "online_feature_dim",
                      "online_hidden_dim", "online_slo_ms",
                      "online_flush_ms", "online_bucket_sizes")
#: the serving microbench's config identity: runs are only regression-
#: compared within the same ingest representation AND bucket geometry —
#: rows/sec across different bucket sets (or arrow- vs row-shaped
#: partitions) are different experiments
_SERVE_IDENT_KEYS = ("serve_ingest", "serve_rows_total", "serve_batch_size",
                     "serve_row_bytes", "serve_bucket_sizes")
_DECODE_KEY = "decode_tokens_per_sec"
#: the decode microbench's config identity: aggregate tokens/sec is only
#: comparable at the same model geometry, page/slot/pool geometry (the
#: scheduling surface), request volume, generation length, SLOs AND
#: device/CPU counts — a decode step over different slots or pages is a
#: different experiment, and TTFT/ITL latencies are only comparable at
#: the same everything
_DECODE_IDENT_KEYS = ("decode_clients", "decode_requests",
                      "decode_max_new_tokens", "decode_prompt_lens",
                      "decode_model", "decode_page_size",
                      "decode_max_seqs", "decode_prefill_buckets",
                      "decode_ttft_slo_ms", "decode_itl_slo_ms",
                      "decode_devices", "decode_host_cpus")
_FLEET_KEY = "fleet_overhead_frac"
#: the fleet microbench's config identity: the collector's router-p99
#: cost and its detection latency are only comparable at the same
#: replica/client counts, request volume, scrape cadence and host CPU
#: count (the scrape thread competes with routing for cores)
_FLEET_IDENT_KEYS = ("fleet_replicas", "fleet_clients",
                     "fleet_rows_total", "fleet_scrape_interval_s",
                     "fleet_host_cpus")
_INCIDENT_KEY = "incident_overhead_frac"
#: the incident microbench's config identity: the journal's router-p99
#: cost is only comparable at the same replica/client counts, request
#: volume and host CPU count
_INCIDENT_IDENT_KEYS = ("incident_replicas", "incident_clients",
                        "incident_rows_total", "incident_host_cpus")
_COLLECTIVES_KEY = "collectives_bytes_ratio"
#: the collectives comparison's config identity: the analytic exchange
#: ratio is a function of the parameter tree, the scatter world (device
#: count — the model evaluates at max(devices, 8)), the DCN tier split,
#: the eligibility/bucket sizing, and whether the sharded update is even
#: on — a ratio computed under any other config is a different experiment
_COLLECTIVES_IDENT_KEYS = ("collectives_platform", "collectives_devices",
                           "collectives_dcn_world", "collectives_model",
                           "collectives_grad_mb", "collectives_bucket_mb",
                           "collectives_update_shard")
_DECODE_PREFILL_KEY = "decode_prefill_short_ttft_ms_p99"
#: the chunked-prefill microbench's config identity: short-prompt TTFT
#: p99 and the page-allocation A/B are only comparable at the same
#: prompt mix (short/long lengths, shared-prefix length and volume),
#: chunk ladder, page/slot geometry, model geometry AND device/CPU
#: counts — a packed prefill over a different chunk rung or prompt mix
#: is a different experiment
_DECODE_PREFILL_IDENT_KEYS = (
    "decode_prefill_clients", "decode_prefill_requests",
    "decode_prefill_shared_requests", "decode_prefill_max_new_tokens",
    "decode_prefill_prompt_lens", "decode_prefill_prefix_len",
    "decode_prefill_chunk", "decode_prefill_chunks",
    "decode_prefill_model", "decode_prefill_page_size",
    "decode_prefill_max_seqs", "decode_prefill_devices",
    "decode_prefill_host_cpus")
_DECODE_SPEC_KEY = "spec_itl_p99_ratio"
#: the speculative-decoding A/B's config identity: the ITL ratio,
#: tokens-per-verify-step and acceptance rate are only comparable at
#: the same drafter kind and draft depth k (the mechanism itself),
#: prompt mix, generation length, chunk/page/slot geometry, model
#: geometry AND device/CPU counts — drafts verified over a different
#: ladder or by a different drafter are a different experiment
_DECODE_SPEC_IDENT_KEYS = (
    "spec_clients", "spec_requests", "spec_shared_requests",
    "spec_max_new_tokens", "spec_prompt_lens", "spec_prefix_len",
    "spec_k", "spec_drafter", "spec_ladder", "spec_model",
    "spec_page_size", "spec_max_seqs", "spec_prefill_chunk",
    "spec_devices", "spec_host_cpus")
_COSTS_KEY = "costs_conservation_ratio"
#: the cost-accounting microbench's config identity: the ledger's
#: overhead and the skew detection latency are only comparable at the
#: same tenant/client counts, request volume, judgment cadence and host
#: CPU count (apportionment rides the engines' own threads)
_COSTS_IDENT_KEYS = ("costs_tenants", "costs_clients",
                     "costs_rows_total", "costs_cadence_s",
                     "costs_host_cpus")
#: decode latency p99s regression-gated LOWER-is-better beside the
#: throughput (a scheduler change that buys tokens/sec by doubling the
#: tail is a regression, not a win)
_DECODE_LATENCY_KEYS = ("decode_ttft_ms_p99", "decode_itl_ms_p99")

#: (metric key, breakdown key) pairs the flight requirement covers: a
#: healthy metric value must carry its stage decomposition; a null metric
#: (already explained by its reason field) owes none
_FLIGHT_BREAKDOWNS = ((_FEED_KEY, "feed_stage_breakdown"),
                      (_SERVE_KEY, "serve_stage_breakdown"),
                      (_ONLINE_KEY, "online_stage_breakdown"),
                      (_DECODE_KEY, "decode_stage_breakdown"))


def validate_breakdown(half: dict[str, Any], metric_key: str,
                       breakdown_key: str, *, required: bool,
                       tolerance: float = DEFAULT_FLIGHT_TOLERANCE
                       ) -> list[str]:
    """Schema + reconciliation problems of one stage breakdown.

    A breakdown must name a bottleneck ``verdict`` and its additive
    ``stage_sum_s`` must reconcile with ``wall_s`` within ``tolerance`` —
    a decomposition that does not add up to the wall it claims to explain
    fails the artifact rather than decorating it.  Only judged when the
    owning metric is a number (an explicit-null metric already carries its
    reason) and when either ``required`` (r09+) or the breakdown is
    present anyway.
    """
    problems: list[str] = []
    if not isinstance(half.get(metric_key), (int, float)):
        return problems
    bd = half.get(breakdown_key)
    if bd is None:
        # a run with the recorder opted out (TFOS_FLIGHT=0) cannot
        # decompose its wall — an explicit null + reason satisfies, same
        # contract as every other schema-total field
        if required and f"{breakdown_key}_reason" not in half:
            problems.append(
                f"missing {breakdown_key!r} (stage-time attribution is "
                "part of the schema from r09: every healthy "
                f"{metric_key!r} must ship the decomposition that "
                f"produced it, or an explicit null + "
                f"'{breakdown_key}_reason')")
        return problems
    if not isinstance(bd, dict):
        return [f"{breakdown_key!r} must be an object"]
    if not bd.get("verdict"):
        problems.append(f"{breakdown_key!r} lacks a bottleneck 'verdict'")
    wall = bd.get("wall_s")
    ssum = bd.get("stage_sum_s")
    if not isinstance(wall, (int, float)) or wall <= 0 \
            or not isinstance(ssum, (int, float)):
        problems.append(
            f"{breakdown_key!r} lacks numeric wall_s/stage_sum_s")
    else:
        frac = ssum / wall
        if abs(frac - 1.0) > tolerance:
            problems.append(
                f"{breakdown_key!r} stage sum {ssum}s is "
                f"{round(frac, 3)}x the measured wall {wall}s — the "
                f"breakdown does not reconcile within ±{tolerance}")
    return problems


def discover(repo_dir: str) -> list[str]:
    """The trajectory: ``BENCH_r*.json`` sorted by round number."""
    paths = glob.glob(os.path.join(repo_dir, "BENCH_r*.json"))
    return sorted(paths, key=_round_of)


def _round_of(path: str) -> int:
    m = re.search(r"r(\d+)\.json$", os.path.basename(path))
    return int(m.group(1)) if m else -1


def load_artifact(path: str) -> dict[str, Any]:
    """Parse one wrapper; returns {"path", "n", "parsed", "problems"}."""
    out: dict[str, Any] = {"path": path, "n": _round_of(path),
                           "parsed": None, "problems": []}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        out["problems"].append(f"cannot read/parse: {e}")
        return out
    if not isinstance(doc, dict):
        out["problems"].append("wrapper must be a JSON object")
        return out
    for key in ("cmd", "rc", "parsed"):
        if key not in doc:
            out["problems"].append(f"wrapper missing {key!r}")
    if isinstance(doc.get("n"), int):
        out["n"] = doc["n"]
    parsed = doc.get("parsed")
    if parsed is not None and not isinstance(parsed, dict):
        out["problems"].append("'parsed' must be an object or null")
        parsed = None
    out["parsed"] = parsed
    return out


def halves(parsed: dict[str, Any]) -> list[tuple[str, dict[str, Any]]]:
    """A headline artifact carries two results: primary + "secondary"."""
    out = [("primary", parsed)]
    sec = parsed.get("secondary")
    if isinstance(sec, dict):
        out.append(("secondary", sec))
    return out


def validate_half(half: dict[str, Any], *,
                  require_roofline: bool,
                  require_feed: bool = False,
                  require_serving: bool = False,
                  require_recovery: bool = False,
                  require_online: bool = False,
                  require_trace: bool = False,
                  require_mesh: bool = False,
                  require_step: bool = False,
                  require_coldstart: bool = False,
                  require_decode: bool = False,
                  require_fleet: bool = False,
                  require_incident: bool = False,
                  require_collectives: bool = False,
                  require_costs: bool = False,
                  require_decode_prefill: bool = False,
                  require_decode_spec: bool = False) -> list[str]:
    """Schema problems of one measured result (a wrapper's half)."""
    problems = []
    for key in _REQUIRED_HALF_KEYS:
        if key not in half:
            problems.append(f"missing {key!r}")
    if "value" in half and not isinstance(half["value"], (int, float)):
        problems.append(f"'value' must be numeric, got {half['value']!r}")
    if "degraded" in half and not isinstance(half["degraded"], str):
        problems.append("'degraded' must be a reason string")
    present = [k for k in _ROOFLINE_KEYS if k in half]
    if require_roofline or present:
        for k in _ROOFLINE_KEYS:
            if k not in half:
                problems.append(
                    f"missing {k!r} (schema is total: measure it or stamp "
                    "an explicit null + reason)")
            elif half[k] is None and f"{k.split('_gbps')[0]}_reason" not \
                    in half and "degraded" not in half:
                problems.append(
                    f"{k!r} is null without a "
                    f"'{k.split('_gbps')[0]}_reason'")
    # feed-transport microbench: host-side, so required even when the
    # accelerator halves degraded — but a degraded run may legitimately
    # have spent its wall budget, so null + reason always satisfies
    if require_feed or _FEED_KEY in half:
        if _FEED_KEY not in half:
            problems.append(
                f"missing {_FEED_KEY!r} (feed microbench is part of the "
                "schema from r07: measure it or stamp an explicit null + "
                "'feed_transport_reason')")
        elif half[_FEED_KEY] is None and "feed_transport_reason" not in half:
            problems.append(
                f"{_FEED_KEY!r} is null without a 'feed_transport_reason'")
        elif (isinstance(half.get(_FEED_KEY), (int, float))
              and "feed_transport" not in half):
            problems.append(
                f"{_FEED_KEY!r} without 'feed_transport' attribution "
                "(shm|pickle) — transports are different experiments")
    # serving microbench: host-side like the feed one — required even on
    # accelerator-degraded runs; null + reason always satisfies
    if require_serving or _SERVE_KEY in half:
        if _SERVE_KEY not in half:
            problems.append(
                f"missing {_SERVE_KEY!r} (serving microbench is part of "
                "the schema from r08: measure it or stamp an explicit "
                "null + 'serve_reason')")
        elif half[_SERVE_KEY] is None and "serve_reason" not in half:
            problems.append(
                f"{_SERVE_KEY!r} is null without a 'serve_reason'")
        elif (isinstance(half.get(_SERVE_KEY), (int, float))
              and "serve_ingest" not in half):
            problems.append(
                f"{_SERVE_KEY!r} without 'serve_ingest' attribution "
                "(arrow|rows) — ingest representations are different "
                "experiments")
    # recovery microbench (elastic membership): host-side like the feed
    # and serving ones — required on primary from r10 even when the
    # accelerator halves degraded; null + 'recovery_reason' always
    # satisfies (degraded runs legitimately spend their wall budget)
    if require_recovery or _RECOVERY_KEY in half:
        if _RECOVERY_KEY not in half:
            problems.append(
                f"missing {_RECOVERY_KEY!r} (recovery microbench is part "
                "of the schema from r10: measure it or stamp an explicit "
                "null + 'recovery_reason')")
        elif half[_RECOVERY_KEY] is None and "recovery_reason" not in half:
            problems.append(
                f"{_RECOVERY_KEY!r} is null without a 'recovery_reason'")
        elif isinstance(half.get(_RECOVERY_KEY), (int, float)):
            missing = [k for k in _RECOVERY_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_RECOVERY_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — recovery times are only "
                    "comparable within one cluster/cadence/kill config")
    # online-serving microbench (continuous-batching tier): host-side like
    # the others — required on primary from r11 even on degraded rounds;
    # null + 'online_reason' always satisfies.  A numeric value must carry
    # its p99-bound config identity AND prove the SLO was met — a rows/sec
    # sustained at an SLO the run missed is not a measurement
    if require_online or _ONLINE_KEY in half:
        if _ONLINE_KEY not in half:
            problems.append(
                f"missing {_ONLINE_KEY!r} (online-serving microbench is "
                "part of the schema from r11: measure it or stamp an "
                "explicit null + 'online_reason')")
        elif half[_ONLINE_KEY] is None and "online_reason" not in half:
            problems.append(
                f"{_ONLINE_KEY!r} is null without an 'online_reason'")
        elif isinstance(half.get(_ONLINE_KEY), (int, float)):
            missing = [k for k in _ONLINE_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_ONLINE_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — closed-loop rows/sec is "
                    "only comparable within one client/geometry/SLO "
                    "config")
            p99 = half.get("online_p99_ms")
            slo = half.get("online_slo_ms")
            if not isinstance(p99, (int, float)):
                problems.append(
                    f"{_ONLINE_KEY!r} without its measured "
                    "'online_p99_ms' — the number is only meaningful AT "
                    "its p99")
            elif isinstance(slo, (int, float)) and p99 > slo:
                problems.append(
                    f"online_p99_ms {p99} exceeds online_slo_ms {slo}: a "
                    "throughput claimed at an SLO it missed is not a "
                    "measurement")
    # serving-mesh microbench (multi-host tier): host-side like the
    # others — required on primary from r13 even on degraded rounds;
    # null + 'mesh_reason' always satisfies.  A numeric value must carry
    # its config identity, its scale efficiency (the claim the mesh
    # exists to make), and prove the SLO was met
    if require_mesh or _MESH_KEY in half:
        if _MESH_KEY not in half:
            problems.append(
                f"missing {_MESH_KEY!r} (serving-mesh microbench is part "
                "of the schema from r13: measure it or stamp an explicit "
                "null + 'mesh_reason')")
        elif half[_MESH_KEY] is None and "mesh_reason" not in half:
            problems.append(
                f"{_MESH_KEY!r} is null without a 'mesh_reason'")
        elif isinstance(half.get(_MESH_KEY), (int, float)):
            missing = [k for k in _MESH_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_MESH_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — aggregate rows/sec is "
                    "only comparable within one replica/geometry/SLO/"
                    "CPU-count config")
            if not isinstance(half.get("mesh_scale_efficiency"),
                              (int, float)):
                problems.append(
                    f"{_MESH_KEY!r} without a numeric "
                    "'mesh_scale_efficiency' — the aggregate number is "
                    "only meaningful against the single-process "
                    "baseline it scales from")
            p99 = half.get("mesh_p99_ms")
            slo = half.get("mesh_slo_ms")
            if not isinstance(p99, (int, float)):
                problems.append(
                    f"{_MESH_KEY!r} without its measured 'mesh_p99_ms' "
                    "— the number is only meaningful AT its p99")
            elif isinstance(slo, (int, float)) and p99 > slo:
                problems.append(
                    f"mesh_p99_ms {p99} exceeds mesh_slo_ms {slo}: a "
                    "throughput claimed at an SLO it missed is not a "
                    "measurement")
    # step-collectives A/B (bucketed gradient exchange): runs on the local
    # device set, so a degraded-accelerator round still owes it (its CPU
    # devices measured the same step structure); null + 'step_reason'
    # always satisfies (a single-device box has nothing to bucket).  A
    # numeric value must carry its monolithic A/B partner, its config
    # identity, a PASSING output-equality check, and its overlap fraction
    # (or that fraction's explicit null + reason)
    if require_step or _STEP_KEY in half:
        if half.get("step_output_equality") == "fail":
            # judged FIRST: a diverged bucketed step also stamps null
            # throughput + reason, and that legitimate-looking null must
            # not launder a broken step into a passing artifact
            problems.append(
                "step_output_equality is 'fail': the bucketed step "
                "produced different losses than the monolithic step — "
                "broken, not fast; the artifact fails")
        if _STEP_KEY not in half:
            problems.append(
                f"missing {_STEP_KEY!r} (step-collectives A/B is part of "
                "the schema from r14: measure it or stamp an explicit "
                "null + 'step_reason')")
        elif half[_STEP_KEY] is None and "step_reason" not in half:
            problems.append(
                f"{_STEP_KEY!r} is null without a 'step_reason'")
        elif isinstance(half.get(_STEP_KEY), (int, float)):
            missing = [k for k in _STEP_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_STEP_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — bucketed-step rows/sec is "
                    "only comparable within one platform/device-count/"
                    "model/batch/bucket config")
            if not isinstance(half.get("step_rows_per_sec_monolithic"),
                              (int, float)):
                problems.append(
                    f"{_STEP_KEY!r} without a numeric "
                    "'step_rows_per_sec_monolithic' — the bucketed number "
                    "is only meaningful against the monolithic step "
                    "A/B'd in the same run")
            if half.get("step_output_equality") != "pass":
                problems.append(
                    "step_output_equality is "
                    f"{half.get('step_output_equality')!r}: a bucketed "
                    "step whose losses were not verified equal to the "
                    "monolithic step's is broken, not fast")
            ovf = half.get("allreduce_overlap_frac")
            if ovf is None:
                if "allreduce_overlap_reason" not in half:
                    problems.append(
                        "'allreduce_overlap_frac' is null without an "
                        "'allreduce_overlap_reason'")
            elif not isinstance(ovf, (int, float)) \
                    or not -1.0 <= ovf <= 1.0:
                problems.append(
                    f"'allreduce_overlap_frac' {ovf!r} is not a fraction "
                    "in [-1, 1] — it is 1 - exposed/ideal-serial comm "
                    "time")
    # compile-cache cold-start A/B: host-side CPU subprocesses like the
    # recovery microbench, so a degraded-accelerator round still owes it;
    # null + 'coldstart_reason' always satisfies.  A numeric value must
    # carry its cache-off partner, proof the cached arm actually hit disk,
    # and its config identity
    if require_coldstart or _COLDSTART_KEY in half:
        if _COLDSTART_KEY not in half:
            problems.append(
                f"missing {_COLDSTART_KEY!r} (compile-cache cold-start "
                "A/B is part of the schema from r15: measure it or stamp "
                "an explicit null + 'coldstart_reason')")
        elif half[_COLDSTART_KEY] is None and "coldstart_reason" not in half:
            problems.append(
                f"{_COLDSTART_KEY!r} is null without a 'coldstart_reason'")
        elif isinstance(half.get(_COLDSTART_KEY), (int, float)):
            missing = [k for k in _COLDSTART_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_COLDSTART_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — cold-start seconds are "
                    "only comparable within one platform/geometry/"
                    "ladder/CPU-count config")
            if not isinstance(half.get("coldstart_seconds_nocache"),
                              (int, float)):
                problems.append(
                    f"{_COLDSTART_KEY!r} without a numeric "
                    "'coldstart_seconds_nocache' — the cached number is "
                    "only meaningful against the cache-off cold start "
                    "A/B'd in the same run")
            hits = half.get("coldstart_disk_hits")
            if not isinstance(hits, (int, float)) or hits <= 0:
                problems.append(
                    f"{_COLDSTART_KEY!r} with coldstart_disk_hits "
                    f"{hits!r}: a 'cached' cold start that took no disk "
                    "hits did not measure the cache")
    # generative-decode A/B (token-level continuous batching): host-side
    # like the other serving microbenches, so a degraded-accelerator
    # round still owes it; null + 'decode_reason' always satisfies.  A
    # numeric value must carry its sequential A/B partner, its config
    # identity, a PASSING token-level output-equality check, and both
    # latency p99s under their SLOs — a tokens/sec claimed at an SLO the
    # run missed (or with diverging tokens) is not a measurement
    if require_decode or _DECODE_KEY in half:
        if half.get("decode_output_equality") == "fail":
            # judged FIRST: a diverged concurrent decode also stamps
            # null throughput + reason, and that legitimate-looking null
            # must not launder broken batching into a passing artifact
            problems.append(
                "decode_output_equality is 'fail': continuous batching "
                "produced different tokens than sequential decode — "
                "broken, not fast; the artifact fails")
        if _DECODE_KEY not in half:
            problems.append(
                f"missing {_DECODE_KEY!r} (generative-decode microbench "
                "is part of the schema from r16: measure it or stamp an "
                "explicit null + 'decode_reason')")
        elif half[_DECODE_KEY] is None and "decode_reason" not in half:
            problems.append(
                f"{_DECODE_KEY!r} is null without a 'decode_reason'")
        elif isinstance(half.get(_DECODE_KEY), (int, float)):
            missing = [k for k in _DECODE_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_DECODE_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — decode tokens/sec is only "
                    "comparable within one model/page/slot/SLO/device "
                    "config")
            if not isinstance(half.get("decode_tokens_per_sec_sequential"),
                              (int, float)):
                problems.append(
                    f"{_DECODE_KEY!r} without a numeric "
                    "'decode_tokens_per_sec_sequential' — the batched "
                    "number is only meaningful against the sequential "
                    "per-request decode A/B'd in the same run")
            if half.get("decode_output_equality") != "pass":
                problems.append(
                    "decode_output_equality is "
                    f"{half.get('decode_output_equality')!r}: a "
                    "continuous-batched decode whose tokens were not "
                    "verified equal to sequential decode's is broken, "
                    "not fast")
            for lkey, slo_key, what in (
                    ("decode_ttft_ms_p99", "decode_ttft_slo_ms",
                     "time-to-first-token"),
                    ("decode_itl_ms_p99", "decode_itl_slo_ms",
                     "inter-token latency")):
                p99 = half.get(lkey)
                slo = half.get(slo_key)
                if not isinstance(p99, (int, float)):
                    problems.append(
                        f"{_DECODE_KEY!r} without its measured "
                        f"'{lkey}' — the number is only meaningful AT "
                        f"its {what} p99")
                elif isinstance(slo, (int, float)) and p99 > slo:
                    problems.append(
                        f"{lkey} {p99} exceeds {slo_key} {slo}: a "
                        "tokens/sec claimed at an SLO it missed is not "
                        "a measurement")
    # chunked-prefill + COW prefix-sharing microbench: host-side like
    # the decode one, so a degraded-accelerator round still owes it;
    # null + 'decode_prefill_reason' always satisfies.  A numeric
    # short-prompt TTFT p99 must carry its config identity, a PASSING
    # token-level equality check against the per-prompt engine, and its
    # page-allocation A/B (the sub-linear unique-pages claim); the TTFT
    # speedup may be null only WITH a
    # 'decode_prefill_short_ttft_speedup_reason' — a compute-bound
    # single-device host pays real FLOPs for the packed fixed-shape
    # geometry a dispatch-bound accelerator gets for ~one slot's cost
    if require_decode_prefill or _DECODE_PREFILL_KEY in half:
        if half.get("decode_prefill_output_equality") == "fail":
            # judged FIRST: a diverged chunked prefill also stamps a
            # null headline + reason, and that legitimate-looking null
            # must not launder broken sharing into a passing artifact
            problems.append(
                "decode_prefill_output_equality is 'fail': chunked "
                "prefill with prefix sharing decoded different tokens "
                "than per-prompt prefill — broken, not fast; the "
                "artifact fails")
        if _DECODE_PREFILL_KEY not in half:
            problems.append(
                f"missing {_DECODE_PREFILL_KEY!r} (chunked-prefill "
                "microbench is part of the schema from r21: measure it "
                "or stamp an explicit null + 'decode_prefill_reason')")
        elif half[_DECODE_PREFILL_KEY] is None \
                and "decode_prefill_reason" not in half:
            problems.append(
                f"{_DECODE_PREFILL_KEY!r} is null without a "
                "'decode_prefill_reason'")
        elif isinstance(half.get(_DECODE_PREFILL_KEY), (int, float)):
            missing = [k for k in _DECODE_PREFILL_IDENT_KEYS
                       if k not in half]
            if missing:
                problems.append(
                    f"{_DECODE_PREFILL_KEY!r} without its config "
                    f"identity ({', '.join(missing)}) — short-prompt "
                    "TTFT is only comparable within one "
                    "mix/chunk/page/slot/device config")
            if "decode_prefill_reason" not in half:
                # a reason (e.g. wall budget exhausted after the
                # chunked pass) waives the A/B partner requirements —
                # the raw chunked numbers still stand on their own
                if half.get("decode_prefill_output_equality") != "pass":
                    problems.append(
                        "decode_prefill_output_equality is "
                        f"{half.get('decode_prefill_output_equality')!r}"
                        ": a chunked+shared prefill whose tokens were "
                        "not verified equal to per-prompt prefill's is "
                        "broken, not fast")
                for pkey in ("decode_prefill_alloc_pages",
                             "decode_prefill_alloc_pages_baseline",
                             "decode_prefill_page_savings_frac"):
                    if not isinstance(half.get(pkey), (int, float)):
                        problems.append(
                            f"{_DECODE_PREFILL_KEY!r} without a "
                            f"numeric '{pkey}' — the sharing claim is "
                            "only meaningful against the per-prompt "
                            "page allocation A/B'd in the same run")
                if half.get("decode_prefill_short_ttft_speedup") is None \
                        and "decode_prefill_short_ttft_speedup_reason" \
                        not in half:
                    problems.append(
                        "'decode_prefill_short_ttft_speedup' is null "
                        "without a "
                        "'decode_prefill_short_ttft_speedup_reason'")
    # speculative-decoding microbench: host-side like the chunked-prefill
    # one, so required even on degraded-accelerator rounds; null +
    # 'spec_reason' always satisfies.  A numeric ITL ratio must carry
    # its config identity, a verified token-equality pass, a sane
    # acceptance rate, and tokens-per-step > 1 — speculation that never
    # collapsed a step measured nothing, and speculation that changed
    # the tokens is broken, not fast.  The ITL SPEEDUP may be null only
    # WITH a 'spec_itl_speedup_reason': a compute-bound single-device
    # host pays the (k+1)-position verify FLOPs in full where a
    # dispatch-bound accelerator gets the extra positions for ~one
    # step's dispatch cost
    if require_decode_spec or _DECODE_SPEC_KEY in half:
        if half.get("decode_spec_output_equality") == "fail":
            # judged FIRST: a diverged speculative stream also stamps a
            # null headline + reason, and that legitimate-looking null
            # must not launder broken speculation into a passing
            # artifact
            problems.append(
                "decode_spec_output_equality is 'fail': the "
                "speculative engine decoded different tokens than the "
                "single-token engine — broken, not fast; the artifact "
                "fails")
        if _DECODE_SPEC_KEY not in half:
            problems.append(
                f"missing {_DECODE_SPEC_KEY!r} (speculative-decoding "
                "microbench is part of the schema from r22: measure it "
                "or stamp an explicit null + 'spec_reason')")
        elif half[_DECODE_SPEC_KEY] is None \
                and "spec_reason" not in half:
            problems.append(
                f"{_DECODE_SPEC_KEY!r} is null without a 'spec_reason'")
        elif isinstance(half.get(_DECODE_SPEC_KEY), (int, float)):
            sval = half[_DECODE_SPEC_KEY]
            if sval <= 0:
                problems.append(
                    f"{_DECODE_SPEC_KEY!r} is {sval!r} — a latency "
                    "ratio must be a positive number")
            missing = [k for k in _DECODE_SPEC_IDENT_KEYS
                       if k not in half]
            if missing:
                problems.append(
                    f"{_DECODE_SPEC_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — the speculative A/B is "
                    "only comparable within one drafter/k/mix/page/"
                    "device config")
            if half.get("decode_spec_output_equality") != "pass":
                problems.append(
                    "decode_spec_output_equality is "
                    f"{half.get('decode_spec_output_equality')!r}: a "
                    "speculative stream whose tokens were not verified "
                    "equal to the single-token engine's is broken, not "
                    "fast")
            rate = half.get("spec_acceptance_rate")
            if not isinstance(rate, (int, float)) \
                    or not 0.0 <= rate <= 1.0:
                problems.append(
                    f"{_DECODE_SPEC_KEY!r} without a numeric "
                    "'spec_acceptance_rate' in [0, 1] — an ITL ratio "
                    "with no drafter hit rate cannot be attributed to "
                    "speculation")
            tps = half.get("spec_tokens_per_step")
            if not isinstance(tps, (int, float)) or tps <= 1.0:
                problems.append(
                    f"'spec_tokens_per_step' is {tps!r} — speculation "
                    "must emit MORE than one token per verify step, or "
                    "the mechanism under test never engaged")
            if half.get("spec_itl_speedup") is None \
                    and "spec_itl_speedup_reason" not in half:
                problems.append(
                    "'spec_itl_speedup' is null without a "
                    "'spec_itl_speedup_reason'")
    # fleet-observability microbench: host-side multi-process like the
    # mesh one, so a degraded-accelerator round still owes it; null +
    # 'fleet_reason' always satisfies.  A numeric overhead must be a
    # sane fraction, carry its config identity, prove the induced
    # hot-replica skew was detected within one scrape cadence of the
    # earliest detectable window, and prove the federated exposition
    # validated — a collector whose cost is unbounded, whose detector
    # is slower than the re-balancing loop it feeds, or whose
    # federation emits invalid exposition is not an observability plane
    if require_fleet or _FLEET_KEY in half:
        if _FLEET_KEY not in half:
            problems.append(
                f"missing {_FLEET_KEY!r} (fleet-observability microbench "
                "is part of the schema from r17: measure it or stamp an "
                "explicit null + 'fleet_reason')")
        elif half[_FLEET_KEY] is None and "fleet_reason" not in half:
            problems.append(
                f"{_FLEET_KEY!r} is null without a 'fleet_reason'")
        elif isinstance(half.get(_FLEET_KEY), (int, float)):
            if not -1.0 <= half[_FLEET_KEY] <= 1.0:
                problems.append(
                    f"{_FLEET_KEY!r} {half[_FLEET_KEY]} is not a "
                    "fraction in [-1, 1] — it is (p99_on − p99_off) / "
                    "p99_off")
            missing = [k for k in _FLEET_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_FLEET_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — collector overhead and "
                    "detection latency are only comparable within one "
                    "replica/client/cadence/CPU-count config")
            detect = half.get("fleet_skew_detect_s")
            cadence = half.get("fleet_scrape_interval_s")
            if not isinstance(detect, (int, float)):
                problems.append(
                    f"{_FLEET_KEY!r} without a numeric "
                    "'fleet_skew_detect_s' — the detection claim is the "
                    "plane's whole point")
            elif isinstance(cadence, (int, float)) \
                    and detect > 3 * cadence + 1.0:
                problems.append(
                    f"fleet_skew_detect_s {detect} exceeds "
                    f"3 × {cadence}s cadence + 1s: the load-skew "
                    "finding fired later than one cadence past the "
                    "earliest detectable window")
            if half.get("fleet_metrics_valid") is not True:
                problems.append(
                    "fleet_metrics_valid is "
                    f"{half.get('fleet_metrics_valid')!r}: a federated "
                    "/fleet/metrics that was not schema-validated (or "
                    "failed) cannot back the stamped number")
        elif half[_FLEET_KEY] is not None:
            # neither null nor numeric (e.g. a JSON string): every fleet
            # requirement above hangs off the numeric branch, so without
            # this a forged value would skip the whole r17 block
            problems.append(
                f"{_FLEET_KEY!r} must be numeric or an explicit null "
                f"(got {half[_FLEET_KEY]!r})")
    # incident-plane microbench: host-side multi-process like the fleet
    # one, so a degraded-accelerator round still owes it; null +
    # 'incident_reason' always satisfies.  A numeric overhead must be a
    # sane fraction, carry its config identity, and prove the in-run
    # chaos pass: SIGKILL under load reconstructed into ONE
    # causally-ordered timeline with the death event, the fenced
    # regroup, and an exemplar-linked recovered trace — a journal whose
    # cost is unbounded or whose forensics cannot reconstruct the
    # incident it exists for is not an incident plane
    if require_incident or _INCIDENT_KEY in half:
        if _INCIDENT_KEY not in half:
            problems.append(
                f"missing {_INCIDENT_KEY!r} (incident-plane microbench "
                "is part of the schema from r18: measure it or stamp an "
                "explicit null + 'incident_reason')")
        elif half[_INCIDENT_KEY] is None \
                and "incident_reason" not in half:
            problems.append(
                f"{_INCIDENT_KEY!r} is null without an "
                "'incident_reason'")
        elif isinstance(half.get(_INCIDENT_KEY), (int, float)):
            if not -1.0 <= half[_INCIDENT_KEY] <= 1.0:
                problems.append(
                    f"{_INCIDENT_KEY!r} {half[_INCIDENT_KEY]} is not a "
                    "fraction in [-1, 1] — it is (p99_on − p99_off) / "
                    "p99_off")
            missing = [k for k in _INCIDENT_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_INCIDENT_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — journal overhead is only "
                    "comparable within one replica/client/CPU-count "
                    "config")
            if half.get("incident_timeline_valid") is not True:
                problems.append(
                    "incident_timeline_valid is "
                    f"{half.get('incident_timeline_valid')!r}: a "
                    "SIGKILL chaos pass that was not reconstructed and "
                    "validated in-run cannot back the stamped number")
            if not isinstance(half.get("incident_death_latency_s"),
                              (int, float)):
                problems.append(
                    f"{_INCIDENT_KEY!r} without a numeric "
                    "'incident_death_latency_s' — the forensic horizon "
                    "(SIGKILL → fenced regroup) is part of the claim")
            linked = half.get("incident_linked_traces")
            if not (isinstance(linked, int) and linked >= 1):
                problems.append(
                    "incident_linked_traces is "
                    f"{linked!r}: without ≥1 exemplar-linked recovered "
                    "trace the timeline answers 'what died' but never "
                    "'what the user felt'")
        elif half[_INCIDENT_KEY] is not None:
            # neither null nor numeric: keep the forged-value door shut
            # like the fleet block above
            problems.append(
                f"{_INCIDENT_KEY!r} must be numeric or an explicit null "
                f"(got {half[_INCIDENT_KEY]!r})")
    # sharded-weight-update collectives comparison: the analytic bytes
    # ratio needs no second device, so a degraded-accelerator round
    # still owes it; null + 'collectives_reason' satisfies only for a
    # box where even the model could not run.  A diverged equality check
    # fails the artifact whether or not throughput was stamped
    if require_collectives or _COLLECTIVES_KEY in half:
        if half.get("collectives_equality") == "fail":
            # judged FIRST: a diverged sharded-update step also stamps
            # null throughput + reason, and that legitimate-looking null
            # must not launder a broken step into a passing artifact
            problems.append(
                "collectives_equality is 'fail': the sharded-update step "
                "produced different losses than the all-reduce step — "
                "broken, not fast; the artifact fails")
        if _COLLECTIVES_KEY not in half:
            problems.append(
                f"missing {_COLLECTIVES_KEY!r} (sharded-update "
                "collectives comparison is part of the schema from r19: "
                "measure it or stamp an explicit null + "
                "'collectives_reason')")
        elif half[_COLLECTIVES_KEY] is None \
                and "collectives_reason" not in half:
            problems.append(
                f"{_COLLECTIVES_KEY!r} is null without a "
                "'collectives_reason'")
        elif isinstance(half.get(_COLLECTIVES_KEY), (int, float)):
            if not 0.0 < half[_COLLECTIVES_KEY] < 1.0:
                problems.append(
                    f"{_COLLECTIVES_KEY!r} {half[_COLLECTIVES_KEY]} is "
                    "not strictly inside (0, 1) — a scattered exchange "
                    "that moves as many bytes as the all-reduce it "
                    "replaced is not an optimization")
            missing = [k for k in _COLLECTIVES_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_COLLECTIVES_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — the exchange ratio is "
                    "only comparable within one platform/device-count/"
                    "DCN-world/model/sizing/update-shard config")
            eq = half.get("collectives_equality")
            if eq is None:
                if "collectives_reason" not in half:
                    problems.append(
                        "'collectives_equality' is null without a "
                        "'collectives_reason' — either the two steps "
                        "ran A/B or the half says why they could not")
            elif eq != "pass":
                problems.append(
                    f"collectives_equality is {eq!r}: a sharded-update "
                    "step whose losses were not verified equal to the "
                    "all-reduce step's is broken, not fast")
            if isinstance(half.get("collectives_rows_per_sec"),
                          (int, float)):
                if eq != "pass":
                    problems.append(
                        "'collectives_rows_per_sec' stamped without a "
                        "passing 'collectives_equality' — throughput of "
                        "an unverified step is not a measurement")
                if not isinstance(
                        half.get("collectives_rows_per_sec_allreduce"),
                        (int, float)):
                    problems.append(
                        "'collectives_rows_per_sec' without a numeric "
                        "'collectives_rows_per_sec_allreduce' — the "
                        "sharded number is only meaningful against the "
                        "all-reduce step A/B'd in the same run")
        elif half[_COLLECTIVES_KEY] is not None:
            # neither null nor numeric: keep the forged-value door shut
            # like the fleet/incident blocks above
            problems.append(
                f"{_COLLECTIVES_KEY!r} must be numeric or an explicit "
                f"null (got {half[_COLLECTIVES_KEY]!r})")
    # per-tenant cost-accounting microbench: the conservation ratio is
    # the ledger's load-bearing claim — apportioned tenant seconds plus
    # padding waste must re-add to the engine seconds they were split
    # from, within 1%, or every downstream chargeback line is fiction.
    # Null + 'costs_reason' always satisfies; a numeric ratio must carry
    # its config identity, a bounded ledger overhead, a skew-detection
    # latency inside the judged cadence budget, and a goodput breakdown
    # that reconciles to the measured training wall
    if require_costs or _COSTS_KEY in half:
        if _COSTS_KEY not in half:
            problems.append(
                f"missing {_COSTS_KEY!r} (cost-accounting microbench is "
                "part of the schema from r20: measure it or stamp an "
                "explicit null + 'costs_reason')")
        elif half[_COSTS_KEY] is None and "costs_reason" not in half:
            problems.append(
                f"{_COSTS_KEY!r} is null without a 'costs_reason'")
        elif isinstance(half.get(_COSTS_KEY), (int, float)):
            if abs(half[_COSTS_KEY] - 1.0) > 0.01:
                problems.append(
                    f"{_COSTS_KEY!r} {half[_COSTS_KEY]} drifts more "
                    "than 1% from 1.0 — per-tenant charges plus padding "
                    "waste must conserve the engine seconds they were "
                    "apportioned from")
            missing = [k for k in _COSTS_IDENT_KEYS if k not in half]
            if missing:
                problems.append(
                    f"{_COSTS_KEY!r} without its config identity "
                    f"({', '.join(missing)}) — ledger overhead and skew "
                    "detection latency are only comparable within one "
                    "tenant/client/volume/cadence/CPU-count config")
            ov = half.get("costs_overhead_frac")
            if not (isinstance(ov, (int, float)) and -1.0 <= ov <= 1.0):
                problems.append(
                    f"costs_overhead_frac is {ov!r}: the stamped ratio "
                    "is only admissible next to an A/B-measured ledger "
                    "overhead fraction in [-1, 1]")
            det = half.get("costs_skew_detect_s")
            cad = half.get("costs_cadence_s")
            if not isinstance(det, (int, float)):
                problems.append(
                    f"costs_skew_detect_s is {det!r}: an induced "
                    "dominant tenant that was never caught by "
                    "fleet.cost_skew cannot back the stamped ratio")
            elif isinstance(cad, (int, float)) \
                    and det > 3.0 * cad + 1.0:
                problems.append(
                    f"costs_skew_detect_s {det} exceeds the judged "
                    f"budget of 3x cadence + 1s ({3.0 * cad + 1.0:.1f}s "
                    f"at {cad}s cadence) — a skew finding that lands "
                    "after the spike is an autopsy, not an alert")
            bd = half.get("costs_goodput_breakdown")
            if not isinstance(bd, dict):
                problems.append(
                    f"costs_goodput_breakdown is {bd!r}: the goodput "
                    "ledger's phase breakdown is part of the claim")
            else:
                wall = bd.get("wall_s")
                ssum = bd.get("stage_sum_s")
                if not (isinstance(wall, (int, float))
                        and isinstance(ssum, (int, float))):
                    problems.append(
                        "costs_goodput_breakdown without numeric "
                        "'wall_s' and 'stage_sum_s' — an unreconcilable "
                        "breakdown is a narrative, not a ledger")
                elif wall > 0 and abs(ssum / wall - 1.0) > 0.15:
                    problems.append(
                        f"costs_goodput_breakdown does not reconcile: "
                        f"phases sum to {ssum / wall:.3f} of the "
                        "measured wall (tolerance 0.15) — unattributed "
                        "time beyond the stall residual means a phase "
                        "is missing")
        elif half[_COSTS_KEY] is not None:
            # neither null nor numeric: keep the forged-value door shut
            # like the fleet/incident/collectives blocks above
            problems.append(
                f"{_COSTS_KEY!r} must be numeric or an explicit null "
                f"(got {half[_COSTS_KEY]!r})")
    # request-tracing overhead: A/B-measured on the online path, so a
    # degraded-accelerator round still owes it; null + reason always
    # satisfies (e.g. TFOS_TRACE_REQUESTS=0 runs have no A to B against)
    if require_trace or _TRACE_OVERHEAD_KEY in half:
        if _TRACE_OVERHEAD_KEY not in half:
            problems.append(
                f"missing {_TRACE_OVERHEAD_KEY!r} (measured tracing "
                "overhead is part of the schema from r12: A/B it or "
                "stamp an explicit null + 'trace_overhead_reason')")
        elif half[_TRACE_OVERHEAD_KEY] is None \
                and "trace_overhead_reason" not in half:
            problems.append(
                f"{_TRACE_OVERHEAD_KEY!r} is null without a "
                "'trace_overhead_reason'")
        elif isinstance(half.get(_TRACE_OVERHEAD_KEY), (int, float)) \
                and not -1.0 <= half[_TRACE_OVERHEAD_KEY] <= 1.0:
            problems.append(
                f"{_TRACE_OVERHEAD_KEY!r} {half[_TRACE_OVERHEAD_KEY]} is "
                "not a fraction in [-1, 1] — it is 1 - traced/untraced "
                "throughput")
    return problems


def _comparable_prior(artifacts: list[dict], newest: dict, label: str,
                      half: dict) -> tuple[float, str] | None:
    """Best prior (value, source) for the same metric on the same
    platform AND batch size, non-degraded, timing not suspect.

    Batch size is part of the config identity: a re-baseline that pins a
    different batch (wide_deep 4096→1024, BASELINE.md) must not create
    cross-config comparisons in either direction — steps/sec at two batch
    sizes are different experiments.
    """
    best: tuple[float, str] | None = None
    for art in artifacts:
        if art["n"] >= newest["n"] or not art["parsed"]:
            continue
        for plabel, phalf in halves(art["parsed"]):
            if (phalf.get("metric") != half.get("metric")
                    or phalf.get("platform") != half.get("platform")
                    or phalf.get("batch_size") != half.get("batch_size")
                    or "degraded" in phalf
                    or phalf.get("timing_suspect")
                    or not isinstance(phalf.get("value"), (int, float))):
                continue
            src = f"{os.path.basename(art['path'])}:{plabel}"
            if best is None or phalf["value"] > best[0]:
                best = (float(phalf["value"]), src)
    return best


def _comparable_prior_feed(artifacts: list[dict], newest: dict,
                           half: dict) -> tuple[float, str] | None:
    """Best prior ``feed_rows_per_sec`` under the same transport and feed
    config (chunk/batch/row sizes) — the microbench's config identity.

    The feed number is host-side, so priors whose accelerator halves were
    degraded still count: a CPU-fallback round measured the same data
    plane.  Transports are different experiments (that is the point of the
    attribution) and never compared across."""
    ident_keys = ("feed_transport", "feed_rows_total", "feed_chunk_rows",
                  "feed_batch_size", "feed_row_bytes")
    return _comparable_prior_hostside(artifacts, newest, half,
                                      _FEED_KEY, ident_keys)


def _comparable_prior_serving(artifacts: list[dict], newest: dict,
                              half: dict) -> tuple[float, str] | None:
    """Best prior ``serve_rows_per_sec`` under the same ingest
    representation and bucket geometry (``_SERVE_IDENT_KEYS``).

    Host-side like the feed microbench, so degraded-accelerator priors
    still count — they measured the same serving data plane."""
    return _comparable_prior_hostside(artifacts, newest, half,
                                      _SERVE_KEY, _SERVE_IDENT_KEYS)


def _comparable_prior_online(artifacts: list[dict], newest: dict,
                             half: dict) -> tuple[float, str] | None:
    """Best prior ``online_rows_per_sec`` under the same client count,
    model geometry, bucket ladder and p99 SLO (``_ONLINE_IDENT_KEYS``).
    Host-side like the other microbenches: degraded-accelerator priors
    still count."""
    return _comparable_prior_hostside(artifacts, newest, half,
                                      _ONLINE_KEY, _ONLINE_IDENT_KEYS)


def _comparable_prior_mesh(artifacts: list[dict], newest: dict,
                           half: dict) -> tuple[float, str] | None:
    """Best prior ``mesh_rows_per_sec`` under the same replica/client
    counts, model geometry, SLO and host CPU count
    (``_MESH_IDENT_KEYS``).  Host-side like the other microbenches:
    degraded-accelerator priors still count."""
    return _comparable_prior_hostside(artifacts, newest, half,
                                      _MESH_KEY, _MESH_IDENT_KEYS)


def _comparable_prior_step(artifacts: list[dict], newest: dict,
                           half: dict) -> tuple[float, str] | None:
    """Best prior ``step_rows_per_sec`` under the same platform, device
    count, model geometry, batch and bucket size (``_STEP_IDENT_KEYS``).
    Judged like the other microbenches even on degraded rounds: the local
    device set measured the same step structure."""
    return _comparable_prior_hostside(artifacts, newest, half,
                                      _STEP_KEY, _STEP_IDENT_KEYS)


def _comparable_prior_decode(artifacts: list[dict], newest: dict,
                             half: dict, key: str = _DECODE_KEY,
                             better=max) -> tuple[float, str] | None:
    """Best prior decode metric under the same model/page/slot/SLO/device
    config (``_DECODE_IDENT_KEYS``).  ``key``/``better`` select the
    direction: throughput (``max``) for ``decode_tokens_per_sec``,
    latency (``min``) for the TTFT/ITL p99s.  Host-side like the other
    serving microbenches: degraded-accelerator priors still count."""
    return _comparable_prior_hostside(artifacts, newest, half,
                                      key, _DECODE_IDENT_KEYS,
                                      better=better)


def _comparable_prior_coldstart(artifacts: list[dict], newest: dict,
                                half: dict) -> tuple[float, str] | None:
    """Best (LOWEST — cold start is a latency) prior
    ``coldstart_seconds`` under the same platform/geometry/ladder/CPU
    config.  Host-side like the other microbenches: degraded-accelerator
    priors still count."""
    return _comparable_prior_hostside(artifacts, newest, half,
                                      _COLDSTART_KEY,
                                      _COLDSTART_IDENT_KEYS, better=min)


def _comparable_prior_recovery(artifacts: list[dict], newest: dict,
                               half: dict) -> tuple[float, str] | None:
    """Best (i.e. LOWEST — recovery is a latency) prior
    ``recovery_seconds`` under the same cluster/cadence/kill config.
    Host-side like the other microbenches: degraded-accelerator priors
    still count."""
    return _comparable_prior_hostside(artifacts, newest, half,
                                      _RECOVERY_KEY, _RECOVERY_IDENT_KEYS,
                                      better=min)


def _comparable_prior_collectives(artifacts: list[dict], newest: dict,
                                  half: dict) -> tuple[float, str] | None:
    """Best (i.e. LOWEST — the exchange ratio is bytes moved over bytes
    the all-reduce would move) prior ``collectives_bytes_ratio`` under
    the same platform/device/DCN/model/sizing/update-shard config.  The
    model is host-side arithmetic: degraded-accelerator priors still
    count."""
    return _comparable_prior_hostside(artifacts, newest, half,
                                      _COLLECTIVES_KEY,
                                      _COLLECTIVES_IDENT_KEYS, better=min)


def _comparable_prior_hostside(artifacts: list[dict], newest: dict,
                               half: dict, key: str,
                               ident_keys: tuple[str, ...],
                               better=max) -> tuple[float, str] | None:
    """Best prior value of a host-side microbench metric among runs whose
    config identity (``ident_keys``) matches the newest half's.

    ``better`` picks the comparison direction: ``max`` for throughputs,
    ``min`` for latencies (``recovery_seconds``)."""
    best: tuple[float, str] | None = None
    for art in artifacts:
        if art["n"] >= newest["n"] or not art["parsed"]:
            continue
        for plabel, phalf in halves(art["parsed"]):
            if (not isinstance(phalf.get(key), (int, float))
                    or any(phalf.get(k) != half.get(k)
                           for k in ident_keys)):
                continue
            src = f"{os.path.basename(art['path'])}:{plabel}"
            if (best is None
                    or better(phalf[key], best[0]) == phalf[key]):
                best = (float(phalf[key]), src)
    return best


def gate(paths: list[str], *, threshold: float = DEFAULT_THRESHOLD,
         target_floor: float = DEFAULT_TARGET_FLOOR,
         require_roofline_from: int = DEFAULT_REQUIRE_ROOFLINE_FROM,
         require_feed_from: int = DEFAULT_REQUIRE_FEED_FROM,
         require_serving_from: int = DEFAULT_REQUIRE_SERVING_FROM,
         require_flight_from: int = DEFAULT_REQUIRE_FLIGHT_FROM,
         flight_tolerance: float = DEFAULT_FLIGHT_TOLERANCE,
         require_recovery_from: int = DEFAULT_REQUIRE_RECOVERY_FROM,
         require_online_from: int = DEFAULT_REQUIRE_ONLINE_FROM,
         require_trace_from: int = DEFAULT_REQUIRE_TRACE_FROM,
         require_mesh_from: int = DEFAULT_REQUIRE_MESH_FROM,
         require_step_from: int = DEFAULT_REQUIRE_STEP_FROM,
         require_coldstart_from: int = DEFAULT_REQUIRE_COLDSTART_FROM,
         require_decode_from: int = DEFAULT_REQUIRE_DECODE_FROM,
         require_fleet_from: int = DEFAULT_REQUIRE_FLEET_FROM,
         require_incident_from: int = DEFAULT_REQUIRE_INCIDENT_FROM,
         require_collectives_from: int = DEFAULT_REQUIRE_COLLECTIVES_FROM,
         require_costs_from: int = DEFAULT_REQUIRE_COSTS_FROM,
         require_decode_prefill_from: int = DEFAULT_REQUIRE_DECODE_PREFILL_FROM,
         require_decode_spec_from: int = DEFAULT_REQUIRE_DECODE_SPEC_FROM
         ) -> dict[str, Any]:
    """Run the gate over a trajectory; returns the verdict document."""
    checks: list[dict[str, Any]] = []

    def check(name: str, status: str, detail: str) -> None:
        checks.append({"name": name, "status": status, "detail": detail})

    if not paths:
        check("trajectory", "fail", "no BENCH_r*.json artifacts found")
        return _verdict(checks, None, threshold, target_floor)

    artifacts = [load_artifact(p) for p in paths]
    artifacts.sort(key=lambda a: a["n"])
    newest = artifacts[-1]
    newest_name = os.path.basename(newest["path"])

    for art in artifacts:
        name = os.path.basename(art["path"])
        is_newest = art is newest
        for problem in art["problems"]:
            check(f"schema:{name}", "fail" if is_newest else "warn", problem)
        if art["parsed"] is None and not art["problems"]:
            # rc captures whether the run itself reported failure
            check(f"empty:{name}",
                  "fail" if is_newest else "warn",
                  "artifact carries no parsed result (silently degraded "
                  "run — no number, no reason)" if is_newest else
                  "prior round left no parsed result")
            continue
        if art["parsed"] is None:
            continue
        for label, half in halves(art["parsed"]):
            require_rf = art["n"] >= require_roofline_from
            # the feed/serving microbenches are stamped once per run, on
            # the primary
            require_fd = (label == "primary"
                          and art["n"] >= require_feed_from)
            require_sv = (label == "primary"
                          and art["n"] >= require_serving_from)
            require_rc = (label == "primary"
                          and art["n"] >= require_recovery_from)
            require_on = (label == "primary"
                          and art["n"] >= require_online_from)
            require_tr = (label == "primary"
                          and art["n"] >= require_trace_from)
            require_ms = (label == "primary"
                          and art["n"] >= require_mesh_from)
            require_st = (label == "primary"
                          and art["n"] >= require_step_from)
            require_cs = (label == "primary"
                          and art["n"] >= require_coldstart_from)
            require_dc = (label == "primary"
                          and art["n"] >= require_decode_from)
            require_fo = (label == "primary"
                          and art["n"] >= require_fleet_from)
            require_in = (label == "primary"
                          and art["n"] >= require_incident_from)
            require_co = (label == "primary"
                          and art["n"] >= require_collectives_from)
            require_ct = (label == "primary"
                          and art["n"] >= require_costs_from)
            require_dp = (label == "primary"
                          and art["n"] >= require_decode_prefill_from)
            require_ds = (label == "primary"
                          and art["n"] >= require_decode_spec_from)
            for problem in validate_half(half, require_roofline=require_rf,
                                         require_feed=require_fd,
                                         require_serving=require_sv,
                                         require_recovery=require_rc,
                                         require_online=require_on,
                                         require_trace=require_tr,
                                         require_mesh=require_ms,
                                         require_step=require_st,
                                         require_coldstart=require_cs,
                                         require_decode=require_dc,
                                         require_fleet=require_fo,
                                         require_incident=require_in,
                                         require_collectives=require_co,
                                         require_costs=require_ct,
                                         require_decode_prefill=require_dp,
                                         require_decode_spec=require_ds):
                check(f"schema:{name}:{label}",
                      "fail" if is_newest else "warn", problem)
            # flight breakdowns ride the primary half with the microbench
            # numbers they decompose (judged whenever present; required
            # from r09)
            require_fl = (label == "primary"
                          and art["n"] >= require_flight_from)
            for mkey, bkey in _FLIGHT_BREAKDOWNS:
                for problem in validate_breakdown(
                        half, mkey, bkey, required=require_fl,
                        tolerance=flight_tolerance):
                    check(f"flight:{name}:{label}",
                          "fail" if is_newest else "warn", problem)

    if newest["parsed"] is not None and not newest["problems"]:
        for label, half in halves(newest["parsed"]):
            cname = f"{half.get('metric', label)}"
            # the feed microbench is host-side: a degraded accelerator half
            # still measured the real data plane, so judge it BEFORE the
            # degraded skip short-circuits the half
            if isinstance(half.get(_FEED_KEY), (int, float)):
                fprior = _comparable_prior_feed(artifacts, newest, half)
                fname = f"regression:{_FEED_KEY}"
                fval = float(half[_FEED_KEY])
                if fprior is None:
                    check(fname, "pass",
                          "no comparable prior feed measurement (same "
                          "transport + feed config) — nothing to regress "
                          "against")
                elif fval >= threshold * fprior[0]:
                    check(fname, "pass",
                          f"{fval} vs best prior {fprior[0]} "
                          f"({fprior[1]}): ratio "
                          f"{round(fval / fprior[0], 4)} ≥ {threshold}")
                else:
                    check(fname, "fail",
                          f"{fval} is {round(fval / fprior[0], 4)}× best "
                          f"prior {fprior[0]} ({fprior[1]}) — the data "
                          f"plane regressed below {threshold}")
            # serving microbench: same host-side reasoning as the feed one
            if isinstance(half.get(_SERVE_KEY), (int, float)):
                sprior = _comparable_prior_serving(artifacts, newest, half)
                sname = f"regression:{_SERVE_KEY}"
                sval = float(half[_SERVE_KEY])
                if sprior is None:
                    check(sname, "pass",
                          "no comparable prior serving measurement (same "
                          "ingest + bucket geometry) — nothing to regress "
                          "against")
                elif sval >= threshold * sprior[0]:
                    check(sname, "pass",
                          f"{sval} vs best prior {sprior[0]} "
                          f"({sprior[1]}): ratio "
                          f"{round(sval / sprior[0], 4)} ≥ {threshold}")
                else:
                    check(sname, "fail",
                          f"{sval} is {round(sval / sprior[0], 4)}× best "
                          f"prior {sprior[0]} ({sprior[1]}) — the serving "
                          f"data plane regressed below {threshold}")
            # online-serving microbench: host-side, judged before the
            # degraded skip like the feed/serving ones
            if isinstance(half.get(_ONLINE_KEY), (int, float)):
                oprior = _comparable_prior_online(artifacts, newest, half)
                oname = f"regression:{_ONLINE_KEY}"
                oval = float(half[_ONLINE_KEY])
                if oprior is None:
                    check(oname, "pass",
                          "no comparable prior online measurement (same "
                          "clients + geometry + SLO) — nothing to "
                          "regress against")
                elif oval >= threshold * oprior[0]:
                    check(oname, "pass",
                          f"{oval} vs best prior {oprior[0]} "
                          f"({oprior[1]}): ratio "
                          f"{round(oval / oprior[0], 4)} ≥ {threshold}")
                else:
                    check(oname, "fail",
                          f"{oval} is {round(oval / oprior[0], 4)}× best "
                          f"prior {oprior[0]} ({oprior[1]}) — the online "
                          f"tier regressed below {threshold}")
            # serving-mesh microbench: host-side, judged before the
            # degraded skip like the others
            if isinstance(half.get(_MESH_KEY), (int, float)):
                mprior = _comparable_prior_mesh(artifacts, newest, half)
                mname = f"regression:{_MESH_KEY}"
                mval = float(half[_MESH_KEY])
                if mprior is None:
                    check(mname, "pass",
                          "no comparable prior mesh measurement (same "
                          "replicas + geometry + SLO + host CPUs) — "
                          "nothing to regress against")
                elif mval >= threshold * mprior[0]:
                    check(mname, "pass",
                          f"{mval} vs best prior {mprior[0]} "
                          f"({mprior[1]}): ratio "
                          f"{round(mval / mprior[0], 4)} ≥ {threshold}")
                else:
                    check(mname, "fail",
                          f"{mval} is {round(mval / mprior[0], 4)}× best "
                          f"prior {mprior[0]} ({mprior[1]}) — the mesh "
                          f"tier regressed below {threshold}")
            # step-collectives A/B: judged before the degraded skip like
            # the others (the local device set measured the same step
            # structure either way)
            if isinstance(half.get(_STEP_KEY), (int, float)):
                stprior = _comparable_prior_step(artifacts, newest, half)
                stname = f"regression:{_STEP_KEY}"
                stval = float(half[_STEP_KEY])
                if stprior is None:
                    check(stname, "pass",
                          "no comparable prior step measurement (same "
                          "platform + device count + geometry + bucket) "
                          "— nothing to regress against")
                elif stval >= threshold * stprior[0]:
                    check(stname, "pass",
                          f"{stval} vs best prior {stprior[0]} "
                          f"({stprior[1]}): ratio "
                          f"{round(stval / stprior[0], 4)} ≥ {threshold}")
                else:
                    check(stname, "fail",
                          f"{stval} is {round(stval / stprior[0], 4)}× "
                          f"best prior {stprior[0]} ({stprior[1]}) — the "
                          f"step path regressed below {threshold}")
            # sharded-update collectives ratio: host-side arithmetic,
            # judged before the degraded skip; LOWER is better (it is
            # bytes moved over the all-reduce's bytes) within one
            # platform/device/DCN/model/sizing/update-shard identity
            if isinstance(half.get(_COLLECTIVES_KEY), (int, float)):
                coprior = _comparable_prior_collectives(artifacts, newest,
                                                        half)
                coname = f"regression:{_COLLECTIVES_KEY}"
                coval = float(half[_COLLECTIVES_KEY])
                if coprior is None:
                    check(coname, "pass",
                          "no comparable prior collectives measurement "
                          "(same platform/device/DCN/model/sizing/"
                          "update-shard config) — nothing to regress "
                          "against")
                elif coval * threshold <= coprior[0]:
                    check(coname, "pass",
                          f"{coval} vs best prior {coprior[0]} "
                          f"({coprior[1]}): ratio "
                          f"{round(coval / coprior[0], 4)} ≤ "
                          f"{round(1 / threshold, 4)}")
                else:
                    check(coname, "fail",
                          f"{coval} is {round(coval / coprior[0], 4)}× "
                          f"the best prior {coprior[0]} ({coprior[1]}) — "
                          "the gradient exchange moves more bytes than "
                          f"it used to beyond 1/{threshold}")
            # generative-decode A/B: host-side, judged before the
            # degraded skip like the others — throughput higher-better,
            # the two latency p99s LOWER-better within the same identity
            # (a scheduler that buys tokens/sec with a doubled tail is a
            # regression, not a win)
            if isinstance(half.get(_DECODE_KEY), (int, float)):
                dprior = _comparable_prior_decode(artifacts, newest, half)
                dname = f"regression:{_DECODE_KEY}"
                dval = float(half[_DECODE_KEY])
                if dprior is None:
                    check(dname, "pass",
                          "no comparable prior decode measurement (same "
                          "model/page/slot/SLO/device config) — nothing "
                          "to regress against")
                elif dval >= threshold * dprior[0]:
                    check(dname, "pass",
                          f"{dval} vs best prior {dprior[0]} "
                          f"({dprior[1]}): ratio "
                          f"{round(dval / dprior[0], 4)} ≥ {threshold}")
                else:
                    check(dname, "fail",
                          f"{dval} is {round(dval / dprior[0], 4)}× best "
                          f"prior {dprior[0]} ({dprior[1]}) — the decode "
                          f"tier regressed below {threshold}")
                for lkey in _DECODE_LATENCY_KEYS:
                    if not isinstance(half.get(lkey), (int, float)):
                        continue
                    lprior = _comparable_prior_decode(
                        artifacts, newest, half, key=lkey, better=min)
                    lname = f"regression:{lkey}"
                    lval = float(half[lkey])
                    if lprior is None:
                        check(lname, "pass",
                              "no comparable prior latency measurement "
                              "— nothing to regress against")
                    elif lval * threshold <= lprior[0]:
                        check(lname, "pass",
                              f"{lval}ms vs best prior {lprior[0]}ms "
                              f"({lprior[1]}): ratio "
                              f"{round(lval / lprior[0], 4)} ≤ "
                              f"{round(1 / threshold, 4)}")
                    else:
                        check(lname, "fail",
                              f"{lval}ms is "
                              f"{round(lval / lprior[0], 4)}× the best "
                              f"prior {lprior[0]}ms ({lprior[1]}) — the "
                              f"decode tail slowed beyond 1/{threshold}")
            # chunked-prefill short-prompt TTFT: host-side, a latency,
            # LOWER is better within its own mix/chunk/page/slot/device
            # identity — a prefill packer that buys page sharing with a
            # slower first token is a regression, not a win
            if isinstance(half.get(_DECODE_PREFILL_KEY), (int, float)):
                pprior = _comparable_prior_hostside(
                    artifacts, newest, half, _DECODE_PREFILL_KEY,
                    _DECODE_PREFILL_IDENT_KEYS, better=min)
                pname = f"regression:{_DECODE_PREFILL_KEY}"
                pval = float(half[_DECODE_PREFILL_KEY])
                if pprior is None:
                    check(pname, "pass",
                          "no comparable prior chunked-prefill "
                          "measurement (same mix/chunk/page/slot/device "
                          "config) — nothing to regress against")
                elif pval * threshold <= pprior[0]:
                    check(pname, "pass",
                          f"{pval}ms vs best prior {pprior[0]}ms "
                          f"({pprior[1]}): ratio "
                          f"{round(pval / pprior[0], 4)} ≤ "
                          f"{round(1 / threshold, 4)}")
                else:
                    check(pname, "fail",
                          f"{pval}ms is "
                          f"{round(pval / pprior[0], 4)}× the best "
                          f"prior {pprior[0]}ms ({pprior[1]}) — the "
                          "short-prompt first token slowed beyond "
                          f"1/{threshold}")
            # speculative-decoding ITL ratio: host-side, a latency
            # ratio, LOWER is better within its own drafter/k/mix/
            # page/device identity — a drafter change that buys
            # acceptance with a slower per-token tail is a regression,
            # not a win
            if isinstance(half.get(_DECODE_SPEC_KEY), (int, float)):
                sprior = _comparable_prior_hostside(
                    artifacts, newest, half, _DECODE_SPEC_KEY,
                    _DECODE_SPEC_IDENT_KEYS, better=min)
                sname = f"regression:{_DECODE_SPEC_KEY}"
                sval = float(half[_DECODE_SPEC_KEY])
                if sprior is None:
                    check(sname, "pass",
                          "no comparable prior speculative-decode "
                          "measurement (same drafter/k/mix/page/device "
                          "config) — nothing to regress against")
                elif sval * threshold <= sprior[0]:
                    check(sname, "pass",
                          f"{sval} vs best prior {sprior[0]} "
                          f"({sprior[1]}): ratio "
                          f"{round(sval / sprior[0], 4)} ≤ "
                          f"{round(1 / threshold, 4)}")
                else:
                    check(sname, "fail",
                          f"{sval} is {round(sval / sprior[0], 4)}× "
                          f"the best prior {sprior[0]} ({sprior[1]}) — "
                          "the speculative per-token tail slowed "
                          f"beyond 1/{threshold}")
            # compile-cache cold start: host-side, judged before the
            # degraded skip; LOWER is better (it is a latency), same
            # contract as recovery_seconds
            if isinstance(half.get(_COLDSTART_KEY), (int, float)):
                cprior = _comparable_prior_coldstart(artifacts, newest,
                                                     half)
                csname = f"regression:{_COLDSTART_KEY}"
                csval = float(half[_COLDSTART_KEY])
                if cprior is None:
                    check(csname, "pass",
                          "no comparable prior cold-start measurement "
                          "(same platform/geometry/ladder/CPU config) — "
                          "nothing to regress against")
                elif csval * threshold <= cprior[0]:
                    check(csname, "pass",
                          f"{csval}s vs best prior {cprior[0]}s "
                          f"({cprior[1]}): ratio "
                          f"{round(csval / cprior[0], 4)} ≤ "
                          f"{round(1 / threshold, 4)}")
                else:
                    check(csname, "fail",
                          f"{csval}s is {round(csval / cprior[0], 4)}× "
                          f"the best prior {cprior[0]}s ({cprior[1]}) — "
                          f"fleet cold start slowed beyond 1/{threshold}")
            # recovery microbench: host-side, judged before the degraded
            # skip too.  LOWER is better (it is a latency): the newest run
            # fails when it exceeds the best comparable prior by more than
            # 1/threshold
            if isinstance(half.get(_RECOVERY_KEY), (int, float)):
                rprior = _comparable_prior_recovery(artifacts, newest,
                                                    half)
                rname = f"regression:{_RECOVERY_KEY}"
                rval = float(half[_RECOVERY_KEY])
                if rprior is None:
                    check(rname, "pass",
                          "no comparable prior recovery measurement "
                          "(same cluster/cadence/kill config) — nothing "
                          "to regress against")
                elif rval * threshold <= rprior[0]:
                    check(rname, "pass",
                          f"{rval}s vs best prior {rprior[0]}s "
                          f"({rprior[1]}): ratio "
                          f"{round(rval / rprior[0], 4)} ≤ "
                          f"{round(1 / threshold, 4)}")
                else:
                    check(rname, "fail",
                          f"{rval}s is {round(rval / rprior[0], 4)}× the "
                          f"best prior {rprior[0]}s ({rprior[1]}) — "
                          f"recovery slowed beyond 1/{threshold}")
            if "degraded" in half:
                check(f"degraded:{cname}", "skip",
                      f"newest run degraded ({half['degraded'][:120]}); "
                      "numbers are fallback evidence, not performance")
                continue
            vsb = half.get("vs_baseline")
            if isinstance(vsb, (int, float)):
                if vsb < target_floor:
                    check(f"target:{cname}", "fail",
                          f"vs_baseline {vsb} below floor {target_floor}")
                else:
                    check(f"target:{cname}", "pass",
                          f"vs_baseline {vsb} ≥ floor {target_floor}")
            prior = _comparable_prior(artifacts, newest, label, half)
            if prior is None:
                check(f"regression:{cname}", "pass",
                      "no comparable prior run (same metric+platform, "
                      "non-degraded) — nothing to regress against")
            else:
                best, src = prior
                value = float(half.get("value", 0.0))
                if value >= threshold * best:
                    check(f"regression:{cname}", "pass",
                          f"{value} vs best prior {best} ({src}): "
                          f"ratio {round(value / best, 4)} ≥ {threshold}")
                else:
                    check(f"regression:{cname}", "fail",
                          f"{value} is {round(value / best, 4)}× best "
                          f"prior {best} ({src}) — below {threshold}")

    return _verdict(checks, newest_name, threshold, target_floor)


def _verdict(checks: list[dict], newest: str | None, threshold: float,
             target_floor: float) -> dict[str, Any]:
    statuses = [c["status"] for c in checks]
    if "fail" in statuses:
        verdict = "fail"
    elif "skip" in statuses:
        # ANY degraded half means part of the newest run is fallback
        # evidence that received no regression judgment — a consumer must
        # not mistake a half-degraded run for a fully healthy one
        verdict = "skip"
    else:
        verdict = "pass"
    return {
        "verdict": verdict,
        "newest": newest,
        "threshold": threshold,
        "target_floor": target_floor,
        "num_checks": len(checks),
        "checks": checks,
        "reasons": [f"{c['name']}: {c['detail']}" for c in checks
                    if c["status"] == "fail"],
    }


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("paths", nargs="*",
                   help="explicit BENCH artifact paths (default: discover "
                        "BENCH_r*.json under --repo)")
    p.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    p.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD)
    p.add_argument("--target-floor", type=float,
                   default=DEFAULT_TARGET_FLOOR)
    p.add_argument("--require-roofline-from", type=int,
                   default=DEFAULT_REQUIRE_ROOFLINE_FROM)
    p.add_argument("--require-feed-from", type=int,
                   default=DEFAULT_REQUIRE_FEED_FROM)
    p.add_argument("--require-serving-from", type=int,
                   default=DEFAULT_REQUIRE_SERVING_FROM)
    p.add_argument("--require-flight-from", type=int,
                   default=DEFAULT_REQUIRE_FLIGHT_FROM)
    p.add_argument("--flight-tolerance", type=float,
                   default=DEFAULT_FLIGHT_TOLERANCE)
    p.add_argument("--require-recovery-from", type=int,
                   default=DEFAULT_REQUIRE_RECOVERY_FROM)
    p.add_argument("--require-online-from", type=int,
                   default=DEFAULT_REQUIRE_ONLINE_FROM)
    p.add_argument("--require-trace-from", type=int,
                   default=DEFAULT_REQUIRE_TRACE_FROM)
    p.add_argument("--require-mesh-from", type=int,
                   default=DEFAULT_REQUIRE_MESH_FROM)
    p.add_argument("--require-step-from", type=int,
                   default=DEFAULT_REQUIRE_STEP_FROM)
    p.add_argument("--require-coldstart-from", type=int,
                   default=DEFAULT_REQUIRE_COLDSTART_FROM)
    p.add_argument("--require-decode-from", type=int,
                   default=DEFAULT_REQUIRE_DECODE_FROM)
    p.add_argument("--require-fleet-from", type=int,
                   default=DEFAULT_REQUIRE_FLEET_FROM)
    p.add_argument("--require-incident-from", type=int,
                   default=DEFAULT_REQUIRE_INCIDENT_FROM)
    p.add_argument("--require-collectives-from", type=int,
                   default=DEFAULT_REQUIRE_COLLECTIVES_FROM)
    p.add_argument("--require-costs-from", type=int,
                   default=DEFAULT_REQUIRE_COSTS_FROM)
    p.add_argument("--require-decode-prefill-from", type=int,
                   default=DEFAULT_REQUIRE_DECODE_PREFILL_FROM)
    p.add_argument("--require-decode-spec-from", type=int,
                   default=DEFAULT_REQUIRE_DECODE_SPEC_FROM)
    args = p.parse_args(argv)
    paths = args.paths or discover(args.repo)
    if not paths:
        print(f"bench_gate: no BENCH_r*.json under {args.repo}",
              file=sys.stderr)
        return 2
    doc = gate(paths, threshold=args.threshold,
               target_floor=args.target_floor,
               require_roofline_from=args.require_roofline_from,
               require_feed_from=args.require_feed_from,
               require_serving_from=args.require_serving_from,
               require_flight_from=args.require_flight_from,
               flight_tolerance=args.flight_tolerance,
               require_recovery_from=args.require_recovery_from,
               require_online_from=args.require_online_from,
               require_trace_from=args.require_trace_from,
               require_mesh_from=args.require_mesh_from,
               require_step_from=args.require_step_from,
               require_coldstart_from=args.require_coldstart_from,
               require_decode_from=args.require_decode_from,
               require_fleet_from=args.require_fleet_from,
               require_incident_from=args.require_incident_from,
               require_collectives_from=args.require_collectives_from,
               require_costs_from=args.require_costs_from,
               require_decode_prefill_from=args.require_decode_prefill_from,
               require_decode_spec_from=args.require_decode_spec_from)
    print(json.dumps(doc))
    return 1 if doc["verdict"] == "fail" else 0


if __name__ == "__main__":
    sys.exit(main())
