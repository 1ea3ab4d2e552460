"""Observability subsystem: tracing + structured event log + metrics export.

Three layers over ONE event model (ISSUE 1 tentpole; SURVEY.md §5 notes the
reference had "Python logging ... no metrics registry"):

- **tracing** (:mod:`.trace`) — ``obs.span("cluster.reserve")``
  context-manager / decorator spans and ``obs.event(...)`` instants,
  recorded into a bounded per-process ring buffer and shipped
  executor→driver over the TFManager kv blackboard by a daemon thread
  (never on the recording thread).  A span is the ONE way the training
  path times a stretch: its one pair of clock reads feeds the ring, the
  flight stage its site names (``.flight(recorder, stage)``), ``dur_s``
  for the goodput ledger, and — in a process that has already imported
  JAX, and only there — a ``jax.profiler.TraceAnnotation`` of the same
  name, so the stretch sits in a profiler session's ``.xplane.pb`` on
  the profiler's clock (``obs.clock_offset`` places the ring's other
  processes there);
- **structured event log / Chrome trace** (:mod:`.chrome`) —
  ``TFCluster.dump_trace(path)`` merges every node's events into one
  Chrome-trace-format file (deterministic; schema-checked by
  ``tools/check_trace.py``); ``TFCluster.shutdown()`` writes the same
  document and every process's counters to
  ``<application scratch dir>/obs/trace.json`` and ``counters.json``;
- **metrics export** (:mod:`.registry`) — counters / gauges / histograms
  with Prometheus text exposition and a JSON snapshot, published with the
  step metrics and aggregated by ``TFCluster.metrics()`` /
  ``TFCluster.metrics_prometheus()``.

Plus the measurement-integrity layer on top (ISSUE 3 tentpole):

- **roofline probes** (:mod:`.roofline`) — in-run delivered HBM and
  interconnect bandwidth measurements, stamped into every BENCH JSON and
  mirrored as registry gauges;
- **anomaly attribution** (:mod:`.anomaly`) — driver-side straggler /
  stall detection over the shipped per-node step-time histograms
  (``TFCluster.check_anomalies()``);
- **live endpoint** (:mod:`.httpd`) — ``TFCluster.serve_observability``'s
  stdlib HTTP server (``/metrics`` Prometheus, ``/healthz``, ``/trace``,
  ``/pipeline``).

And the pipeline flight recorder (ISSUE 6 tentpole):

- **flight recorder** (:mod:`.flight`) — always-on per-stage time
  attribution across the training feed and serving data planes, with a
  per-batch bottleneck verdict (feed-starved / device-bound / emit-bound /
  queue-backpressured); rendered live on ``/pipeline``, judged by
  ``TFCluster.check_anomalies()`` (persistent feed starvation is a
  finding), and stamped by ``bench.py`` into every artifact as a
  wall-time-reconciled stage breakdown.  ``TFOS_FLIGHT=0`` disables.

The flight recorder also attributes the continuous-batching online
serving tier (plane ``"online"``:
``wait``/``coalesce``/``pad``/``compute``/``reply``) and the generative
decode tier (plane ``"decode"``: ``wait``/``prefill``/``decode`` with
``prefill_bound``/``decode_bound`` verdicts — the two decode phases have
different remedies, so they classify apart), and those tiers' counters
and latency histograms (per-tenant request seconds; decode TTFT/ITL SLO
histograms) live in the same registry
(:mod:`tensorflowonspark_tpu.online`,
:mod:`tensorflowonspark_tpu.decode`).

And the fleet incident plane (ISSUE 16 tentpole):

- **event journal** (:mod:`.journal`) — every control-plane transition
  (placement flips + applied confirmations, replica join/death/regroup
  with its generation fence, admission sheds, ``slo.burn`` fire/clear,
  compile-cache spools, decode slot lifecycle) appended as a typed event
  with a hybrid ``(gen, ts, node, pid, seq)`` ordering key so one total
  causal order survives clock skew; cadence-flushed through the fs seam
  (``TFOS_JOURNAL_DIR``) so it survives SIGKILL; federated with
  since-cursor pagination on ``GET /fleet/events``; black-box crash
  dumps bundle journal tail + trace ring + flight records + metrics on
  SIGTERM/anomaly; ``tools/incident.py`` merges it all into one
  Perfetto timeline.  ``TFOS_JOURNAL=0`` disables.

And the cost accounting plane (ISSUE 18 tentpole):

- **cost + goodput ledgers** (:mod:`.ledger`) — per-tenant device-second
  / row / token / byte / compile-second apportionment across the online,
  decode, and serve planes (labeled Prometheus families with an
  un-apportioned engine denominator, so Σ tenants ≡ engine busy — the
  conservation identity ``bench.py --costs`` proves), plus a training
  goodput ledger folding flight stages, checkpoint saves, and elastic
  recovery windows into a productive / input_wait / compile /
  checkpoint / recovery / stall wall-clock breakdown; federated into
  ``GET /fleet/costs`` and the ``fleet.cost_skew`` finding, merged into
  chargeback reports by ``tools/costs.py``.  ``TFOS_LEDGER=0``
  disables.

Span names, by layer (one span per layer boundary and batch / partition /
step — never per record, row or chunk):

- cluster and node runtime: ``cluster.reserve``, ``cluster.train``,
  ``cluster.feed_epoch``, ``cluster.shutdown``, ``spark.task_send`` (a
  task's put on its executor's queue, and before it the partition's
  pickling into row batches the first time any job sends it),
  ``executor.start``, ``executor.task`` > ``executor.task_load`` (the
  function chain's load and the row stream's opening: the rows are
  unpickled batch by batch under the task's iterator); counters
  ``spark_partition_batches_sent_total`` (row batches put on executors'
  queues) and ``spark_partition_blobs_reused_total`` (partitions sent
  without pickling);
  ``node.chip_claim``, ``node.manager_start``, ``health.probe``,
  ``node.register_await``, ``node.trainer_spawn``,
  ``node.distributed_init``, ``node.chip_verify``, ``node.map_fun``;
- feed plane, readers: ``reader.batch`` (attr ``ahead``: batches whose
  arrays are being made ahead) > ``reader.parse`` (any wait for the
  arrays made ahead + read + ``parse_fn`` + each NumPy value's one copy
  into its row of the batch's column array), ``reader.stack`` (what is
  then left: ``np.asarray`` of the list columns, the trim of a short
  last batch), ``feed.stage``;
  ``feed.pump_blocked`` (producer on a full queue), ``feed.wait``
  (consumer on an empty one); ``readers.epoch``; counters
  ``reader_columns_direct_total`` / ``reader_columns_stacked_total``, one
  increment a column a batch, say which way the columns went;
- feed plane, Spark consumer (``TFNode.DataFeed``): ``feed.queue_wait``,
  ``feed.ingest``, ``feed.collate``, ``feed.stage``, ``feed.pump_blocked``
  on the pump thread, ``feed.wait`` on the consumer, ``feed.turnround``
  from an ``EndPartition`` off the queue to the next chunk off it;
- feed plane, Spark feeder (``TFSparkNode._TrainFn``, never on JAX):
  ``feeder.task`` > ``feeder.connect``, ``feeder.first_row``,
  ``feeder.send``, ``feeder.drain_wait``;
- trainer: ``trainer.init``, ``trainer.step`` (attr ``step``, a trace id
  of its own) > ``trainer.shard``, ``trainer.dispatch``,
  ``trainer.checkpoint``; ``ckpt.save`` / ``ckpt.restore``; and the
  device's side, written by the trainer's completion watcher from its own
  threads and in the ring only (``trainer._DeviceWatcher``):
  ``trainer.h2d`` (one a batch that was the host's: the staging call's
  start to every staged array ready on the device; attr ``bytes``) and
  ``trainer.device_step`` (one a step, attr ``step``: from the latest of
  the previous step's end, the dispatch's start and the batch's arrival —
  ``after`` says which — to the loss ready; attrs ``input_wait_s``,
  ``dispatch_s``; an upper estimate of the device's time from the host's
  clock, not the device's own);
- JAX's compile path (``compile_cache.py``, the one listener of JAX's
  monitoring; from JAX's own time-span events, in the ring only, children
  of the span open on the compiling thread — ``trainer.dispatch``,
  ``ckpt.restore``, a feed's thread; none in a steady step):
  ``jit.trace`` (attr ``fun``, JAX's ``fun_name``; only a trace of 5 ms
  or more, nested ones inside the trace that holds them), ``jit.lower``
  (attr ``fun``), ``jit.compile`` (attrs ``fun``, ``cache``: ``hit`` with
  ``retrieval_s`` / ``saved_s``, ``miss`` with ``entry_bytes`` /
  ``written``, or ``off``); counters ``jit_traces_total`` (every trace,
  recorded or not) and ``compile_cache_disk_misses_total`` (one a
  ``jit.compile`` that says ``miss``);
- kernels: ``jax.named_scope`` ``forward`` and ``optimizer`` in the
  compiled step (``parallel/train.py``); counters
  ``table_update_rows_steps_total`` / ``table_update_full_steps_total``,
  one increment a wide&deep step, say which execution of the default
  table update the step's shapes chose (``models/widedeep.py``);
  and five pairs, one increment a step of a packed-row decoder that has
  the site, that say whether it ran on its Pallas kernels or as its plain
  form.  One rule and one writer serve all five (``models/kernels.py``:
  ``runs_fused`` of the kernels' module and the shapes, ``step_counters``
  of its answer); each site's own name for the rule is in brackets:
  ``ssm_scan_fused_steps_total`` / ``ssm_scan_plain_steps_total``
  (``granite_hybrid``'s state-space scan, ``models/ssd_pallas.py``;
  ``granite_hybrid.scan_runs_fused``);
  ``attention_fused_steps_total`` / ``attention_plain_steps_total``
  (``packed_rows.document_attention``, ``models/attention_pallas.py``;
  ``attention_runs_fused``) and, beside them,
  ``attention_blocks_visited_total`` of
  ``attention_blocks_reached_total``: the kernels' loops stop at a
  document's edge, so a step on them follows its rows — the (block of
  queries, block of keys) pairs a head's two kernels visit on the step's
  rows, forward and backward, summed over the attention layers each at
  its own window, of what the shapes and the windows alone reach
  (``packed_rows.row_counters`` from ``attention_pallas.visited``; equal
  on rows that are one document each; both 0 where the ``jnp`` form
  runs, whose loops do not follow the documents);
  ``conv_fused_steps_total`` / ``conv_plain_steps_total``
  (``packed_rows.causal_conv`` of ``granite_hybrid``, ``lfm2_moe`` and
  ``kimi_linear``, ``models/conv_pallas.py``; ``conv_runs_fused``);
  ``kda_scan_fused_steps_total`` / ``kda_scan_plain_steps_total``
  (``kimi_linear``'s chunked delta rule, ``models/kda_pallas.py``;
  ``kimi_linear.kda_scan_runs_fused``);
  ``moe_grouped_fused_steps_total`` / ``moe_grouped_plain_steps_total``
  (the routed experts' grouped products of ``mla_moe``, ``lfm2_moe``,
  ``kimi_linear`` and ``mellum_moe`` in the two forms a step takes when a
  layer's slots fit ``moe.tight_rows`` or ``moe.prefix_rows``,
  ``parallel/grouped_pallas.py`` or ``jax.lax.ragged_dot``;
  ``parallel/moe.py::grouped_runs_fused``); what the device decided a
  layer and a step, of ``moe.routing_counters``:
  ``moe_tight_layers_total`` counts the layer-steps whose held slots fitted
  ``moe.tight_rows`` (the smallest of the routed part's three sizes),
  ``moe_overflow_layers_total`` those that passed ``moe.prefix_rows`` and
  took all the slots, on ``ragged_dot``; beside ``moe_slots_total``,
  ``moe_local_slots_total`` and ``moe_busiest_expert_slots_total``.

Also instrumented: elastic regroups (``elastic``), serving
(``serving``, ``pipeline``), roofline probes, and ``bench.py`` (which
writes a trace artifact even for degraded runs, attributing the probe
timeout).  ``TFOS_TRACE=0`` disables recording; spans then still time
their stretch for the flight recorder and the ledger.
"""

from tensorflowonspark_tpu.obs import (  # noqa: F401
    anomaly,
    chrome,
    fleet,
    flight,
    httpd,
    journal,
    ledger,
    roofline,
    trace,
)
from tensorflowonspark_tpu.obs.registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    gauge,
    get_registry,
    histogram,
    merge_snapshots,
    merged_to_prometheus,
    relabel_snapshot,
    snapshot_to_openmetrics,
    snapshot_to_prometheus,
)
from tensorflowonspark_tpu.obs.trace import (  # noqa: F401
    COUNTERS_KV_PREFIX,
    TRACE_KV_PREFIX,
    RequestTrace,
    TraceContext,
    TraceStore,
    Tracer,
    clock_offset,
    collect_blackboard,
    collect_counters,
    collect_dropped,
    complete,
    configure,
    event,
    flush,
    format_traceparent,
    get_trace_store,
    get_tracer,
    merge_request_docs,
    parse_traceparent,
    span,
    trace_context,
    with_context,
)

__all__ = [
    "anomaly", "chrome", "fleet", "flight", "httpd", "journal",
    "roofline", "trace",
    "Counter", "Gauge", "Histogram", "Registry",
    "counter", "gauge", "histogram", "get_registry",
    "merge_snapshots", "merged_to_prometheus", "relabel_snapshot",
    "snapshot_to_prometheus", "snapshot_to_openmetrics",
    "TRACE_KV_PREFIX", "COUNTERS_KV_PREFIX", "Tracer", "clock_offset",
    "collect_blackboard", "collect_counters", "collect_dropped",
    "complete", "configure", "event", "flush", "get_tracer", "span",
    "TraceContext", "RequestTrace", "TraceStore", "get_trace_store",
    "parse_traceparent", "format_traceparent", "merge_request_docs",
    "trace_context", "with_context",
]
