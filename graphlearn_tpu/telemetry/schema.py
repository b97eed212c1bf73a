"""Registry of flight-recorder event kinds.

Exporters (`telemetry.export`), the report CLI, and external
dashboards key off event ``kind`` strings; an unregistered kind is a
consumer that silently sees nothing.  Every ``recorder.emit('<kind>',
...)`` call site must register its kind here — enforced statically by
``tests/test_event_schema.py``, which greps the package for emit call
sites and fails on any kind missing from :data:`EVENT_KINDS` (and on
stale registry entries with no remaining call site, so the table can't
rot in the other direction).

The value documents the emitter and the fields consumers may rely on.
"""
from __future__ import annotations

from typing import Dict

#: kind -> 'emitter: field summary' (the consumer contract)
EVENT_KINDS: Dict[str, str] = {
    'hop.padding':
        'DistNeighborLoader / fused epoch drivers: hop, nodes, '
        'capacity, fill (1 - fill = padding waste)',
    'channel.stall':
        'ChannelTelemetry._timed: op, secs, occupancy, channel',
    'slack.transition':
        'AdaptiveSlack: from_slack, to_slack, reason, drop_rate, '
        "pin_reason ('reversal' when this widen pins the ladder, "
        "else '')",
    'slack.pinned':
        'AdaptiveSlack: slack, drop_rate, pin_reason (why retuning '
        "stopped: 'reversal' = tighten->widen oscillation guard, "
        "'floor' = drop-free at the configured ladder floor)",
    'padding.truncate':
        'utils.padding.pad_1d: requested, size, dropped — a host-side '
        'pad silently cut non-fill entries (capacity bug surfacing; '
        'GLT_STRICT_PADDING=1 raises instead)',
    'dist.exchange':
        'ExchangeTelemetry drains: since-last-drain deltas of '
        'offered/dropped/slots per loss channel',
    'dist.cold_tier':
        'tiered DistFeature drains: lookups (all feature lookups), '
        'cold_lookups (past the hot tier — the cache denominator), '
        'misses (host-served), cache_hits, hit_rate',
    'cache.hit':
        'data.cold_cache consumers (scope=feature|dist|serving|'
        'hetero): count of cold lookups served from the HBM victim '
        'cache this overlay',
    'cache.miss':
        'data.cold_cache consumers: count of cold lookups that paid '
        'the host gather this overlay (admission candidates; '
        'scope=hetero has NO cache yet, so every cold lookup lands '
        'here — the live twin of cold_lookups == cold_misses)',
    'cache.admit':
        'data.cold_cache consumers: rows written into the HBM ring '
        'this overlay (frequency-ranked winners)',
    'cache.evict':
        'data.cold_cache consumers: residents displaced by this '
        "overlay's admissions (CLOCK second-chance victims)",
    'fused.compile':
        'loader.fused._counted_jit: fn, secs',
    'model.trim':
        'models.BasicGNN and models.hetero.RGAT at trace time, once '
        'per compiled program that trims its layers to the hops they '
        'feed: layers, and per layer rows_in, rows_out, edge_slots '
        'computed — of which windowed_slots aggregated by fanout '
        'window (the batch stated hop_windows) and scattered_slots by '
        'the segment path over every edge slot — beside the '
        "batch's table_rows and table_slots (absent = untrimmed; "
        'windowed_slots 0 = the window mechanism did not engage); the '
        'typed model gives each as a dict, rows per node type and '
        'slots per relation (its as_str form)',
    'sample.dedup':
        'ops.unique.emit_dedup for sampler._multihop_sample and '
        '_hetero_multihop at trace time, once per compiled program '
        'whose node tables grow insertion by insertion: insertions, '
        'and per induce_next call its scope (hop<i> or '
        'hop<i>/<relation>), sorted (elements its sort covers: rows '
        'of the table handed in + candidates), table_rows (capacity '
        'returned), candidates (B*k) and gathered (elements that '
        'dedup moves through a permutation gather a[perm]: '
        'ops.unique.GATHERS_PER_DEDUP x sorted, 0 since the sorts '
        'carry their payloads; a record without the field is of the '
        'form that gathered 5 x sorted); absent = tables held at '
        'their final size from the first hop on (the mesh samplers)',
    'feature.layout':
        'data.feature._store_hot at build time, once per Feature that '
        'places a tier on the device: width (the table\'s D), '
        'stored_width (utils.padding.lane_width), dtype, rows, '
        'stored_bytes, padded (stored_width != width: the tier is a '
        'zero-padded copy, else the caller\'s own buffer)',
    'sample.negative':
        'sampler.neighbor_sampler.link_seeds at trace time, once per '
        'compiled program that draws the seeds of a link batch '
        '(FusedLinkEpoch scans, the per-batch _link_seeds): mode '
        '(binary/triplet), req_num (pairs or destinations drawn), '
        'trials, strict, padding, seed_width (2B + the negatives\' '
        'endpoints)',
    'link.batch':
        'sampler.neighbor_sampler.link_seeds beside sample.negative: '
        'mode, batch (seed edges), seed_width, negative_endpoints (seeds '
        'that are negatives\'), and the hop_capacities / hop_windows the '
        'batch states for its expansion',
    'exchange.plan':
        'parallel.FusedDistTreeEpoch at trace time, once per compiled '
        'mesh program: scope, layout (the exchange layout chosen: '
        'dense/compact/hier), slack, num_parts, batch (per device), '
        'and the slots per hop — frontier_ids / frontier_slots (ids '
        'one device offers in each hop\'s frontier exchange, send '
        'slots its buffer holds for them), feature_ids / '
        'feature_slots (the one feature and label gather); slots '
        'over ids is the padding the owners draw and gather over',
    'span.begin':
        'telemetry.spans: name, trace_id, span_id, parent_id, pid, '
        'tid (+caller fields)',
    'span.end':
        'telemetry.spans: same ids as span.begin plus dur '
        '(monotonic-clock seconds) and error',
    'fault.injected':
        'testing.chaos: site, action, nth, arrival (+op/worker/epoch '
        'filters, secs for delays) — one event per fired fault, so a '
        'chaos run reads out of the same stream as the retries and '
        'restarts it caused',
    'rpc.retry':
        'RpcClient.request: op, attempt, addr, error, backoff_secs — '
        'one transport fault absorbed by the resilience layer',
    'producer.restart':
        'MpSamplingProducer.supervise: worker, exitcode, replayed '
        '(unacked batches re-dispatched), restarts, budget',
    'peer.lost':
        'resilience layer (DistClient / DistLoader / supervise): '
        'peer, peer_kind (server|worker), degraded (True = epoch '
        'finished on survivors under GLT_DEGRADED_OK), lost_batches/'
        'outstanding, received, expected',
    'server.shutdown_timeout':
        'DistServer.wait_for_exit: rank, timeout_secs, '
        'clients_never_exited, clients_left, live_producers — a '
        'shutdown wait that expired instead of returning silently',
    'snapshot.save':
        'utils.checkpoint.SnapshotManager.save: index, ok, secs, dir, '
        'epoch, next_chunk (ok=False carries error — a failed '
        'snapshot write is absorbed, not fatal)',
    'snapshot.restore':
        'utils.checkpoint.SnapshotManager.restore_latest: index, '
        'secs, dir, epoch, next_chunk — one event per data-plane '
        'restore (resume and degraded rollback both land here)',
    'mesh.stall':
        'resilience.run_with_deadline: scope, deadline_secs, healthy '
        '(last-known-healthy process set) — a fused/mesh dispatch '
        'exceeded GLT_DISPATCH_DEADLINE and was converted into a '
        'typed MeshStallError instead of hanging the epoch',
    'serving.request':
        'serving.frontend executor, one per de-multiplexed request: '
        'seeds, bucket, coalesced (requests in the dispatch), ok, '
        'latency_ms (arrival -> resolve; the percentile-table '
        'source), error when ok=False',
    'serving.coalesce':
        'serving.frontend executor, one per coalesced dispatch: '
        'requests, seeds, bucket (chosen capacity), waited_ms since '
        "the run's first arrival (how much of GLT_SERVING_MAX_WAIT_MS "
        'actually bound)',
    'serving.admit':
        'serving.admission.AdmissionController.submit: seeds, '
        'queue_depth after admit, deadline_ms — one per admitted '
        'request',
    'gns.bias':
        'DistNeighborSampler.step_for_batch (GNS mode, build time): '
        'batch, boost, num_parts — one event per compiled GNS step, '
        'recording the cached-neighbor boost that step samples with',
    'gns.sketch_update':
        'DistNeighborSampler._gns_arrays: scope, residents, version, '
        'mask_bytes — one event per cached-set bitmask refresh (the '
        'sketch-selected cold-cache residents ∪ hot split became the '
        'new sampling-bias membership table)',
    'serving.shed':
        'serving.admission: reason (queue_full|deadline|too_large|'
        'draining|shutdown), seeds, queue_depth, limit / waited_ms / '
        'retry_after_ms — one per typed load-shed (the request '
        'future resolves with AdmissionRejected; nothing is silently '
        'dropped; draining sheds are intentional and burn no SLO '
        'budget)',
    'recorder.overflow':
        'telemetry.recorder, ONE-SHOT on the first in-memory ring '
        'drop: ring_capacity — from this point the flight recorder '
        'is a sliding window, not a full history (cumulative count: '
        'stats()["ring_dropped"] / the recorder.ring_dropped gauge)',
    'slo.burn':
        'telemetry.slo.SloTracker: window_secs, burn_rate, p99_ms, '
        'target_p99_ms, qps, count — a sliding window started '
        'consuming latency error budget faster than allotted '
        '(burn_rate crossed 1.0; re-arms when it recovers)',
    'postmortem.dump':
        'telemetry.postmortem.dump: reason, path, events, '
        'error — a post-mortem bundle (recorder ring + metrics '
        'snapshot + health) was written to GLT_POSTMORTEM_DIR',
    'serving.failover':
        'serving.router.FleetRouter: replica, event '
        '(evict|redrive|readmit|exhausted|quarantine|retire), '
        'redriven (in-flight '
        'requests moved to a survivor on evict), state — one event '
        'per fleet state transition / redrive wave, so a failover '
        'reads out of the same stream as the chaos faults that '
        'caused it',
    'serving.swap':
        'serving.swap.hot_swap: version, ok, rolled_back, '
        'parity_max_err, drained_ms — one event per hot model-swap '
        'attempt (ok=False carries error; a parity mismatch rolls '
        'back to the prior version with zero dropped requests; a '
        'never-quiesced executor aborts with rolled_back=False '
        'before any probe ran)',
    'aot.cache_hit':
        'serving.aot_cache.AotExecutableCache: program, bucket, key, '
        'secs — a warm executable deserialized from '
        'GLT_AOT_CACHE_DIR instead of recompiling',
    'aot.cache_miss':
        'serving.aot_cache.AotExecutableCache: program, bucket, key, '
        'reason (absent|stale|corrupt|unreadable|error) — this '
        'bucket paid a compile; corrupt/stale entries land here too '
        '(skip-to-recompile, never a crash or a wrong executable)',
    'ingest.wal_truncate':
        'streaming.wal.WriteAheadLog.open: path, offset, '
        'dropped_bytes, last_seqno — a torn tail (kill mid-append) '
        'was truncated back to the last whole record; replay lands '
        'exactly the whole-record prefix',
    'ingest.replay':
        'streaming.ingest.IngestPipeline.recover: restored (a '
        'compacted base was loaded), replayed_records/_events, '
        'skipped_records (<= the base watermark — the idempotence '
        'that makes a crash between snapshot and WAL reset safe), '
        'applied_seqno, secs — one event per recovery',
    'ingest.compact':
        'streaming.ingest.IngestPipeline.compact: ok, seqno '
        '(watermark baked into the snapshot), events, secs — ok='
        'False is an ABSORBED snapshot-write failure (the WAL keeps '
        'the full history; nothing lost)',
    'ingest.fault':
        'streaming.ingest.IngestPipeline: site (apply|compact|'
        'shard_refresh), '
        'error — an ingestion fault surfaced typed (and dumped a '
        'post-mortem bundle) instead of leaving a half-applied '
        'graph; the WAL replay makes the restart exactly-once',
    'partition.adopt':
        'failover.adopt_shard + the reader recovery seams: '
        'partition, survivor, version, secs (phase=recovered rows '
        'carry the classification→served-batch recovery clock)',
    'partition.book_version':
        'PartitionBook.adopt/.transfer: version, lost, survivor, '
        'num_lanes, planned (True = scheduled handoff cutover, not a '
        'crash adoption) — one per ownership transfer, the routing '
        'authority moving',
    'handoff.transfer':
        'parallel.handoff.handoff: partition, frm, to, phase '
        '(snapshot|transfer|fence|cutover|drain|rollback), version, '
        'secs, error (rollback cause / absorbed drain fault) — one '
        'event per seam of a planned ownership move, so a handoff '
        'reads out of the flight recorder end to end',
    'partition.relabel':
        'parallel.locality.locality_partition: partitioner, '
        'num_parts, num_nodes, seed, edge_cut_frac, max_part_frac, '
        'hotness_weighted — one event per locality relabel build '
        '(the placement decision a dataset was constructed under)',
    'partition.rebalance':
        'parallel.locality.execute_rebalance: partition, frm, to, '
        'demand, version, secs — one event per planned hot-range '
        'migration (each move is a fenced handoff.transfer ladder; '
        'this is the demand-driven WHY on top of it)',
    'exchange.retune':
        'parallel.dist_sampler.ExchangeTelemetry.capacity_retune: '
        'steps, frontier_dest_cap, frontier_traffic_cap, '
        'feature_dest_cap, feature_traffic_cap — the EWMA capacity '
        'model moved a quantized cap and the step cache was cleared '
        '(next dispatch compiles measured per-destination shares)',
    'scale.decision':
        'serving.autoscaler.ElasticController: dir (out|in), outcome '
        '(ok|rolled_back|held:cooldown|held:bounds|held:no_victim), '
        'replica, error, plus the signal snapshot that justified it '
        '(replicas, short_burn, long_burn, queue_frac, headroom_qps) '
        '— every considered scaling decision, acted or held',
    'pallas.dispatch':
        'r19 kernel gates (ops.pallas_sample.sample_one_hop_auto, '
        'data.cold_cache.make_pinned_cold_buffer, streaming.delta.'
        'StreamingGraph._merge_device): kernel (fused_sample|'
        'cold_gather|delta_merge) + per-kernel fields (mode/batch/k, '
        'rows/memory_kind, events/version) — one event per '
        'trace/build that took the Pallas path, so a perf run reads '
        'which arms actually ran the kernel out of the same stream '
        'as its step timings',
    'pallas.fallback':
        'r19 kernel gates (sample + delta sites): kernel, reason '
        '(the unsupported-shape string) + the same per-kernel '
        'fields — the knob was ON but a documented shape rule sent '
        'this call to the XLA/host twin at byte parity; a qualified '
        'kernel that fails to trace, compile or run raises instead '
        'of landing here',
}


#: span NAME vocabulary (the `name` field of span.begin/span.end —
#: the per-stage rows of the report CLI and the Perfetto slices).
#: Same contract as EVENT_KINDS: every ``span('<name>', ...)`` call
#: site registers here, enforced by the same static test.
SPAN_NAMES: Dict[str, str] = {
    'batch':
        'per-batch root span (mesh + host-runtime loaders)',
    'sample.exchange':
        'mesh samplers: the fused sample+exchange SPMD dispatch',
    'feature.lookup':
        'mesh samplers, TIERED stores only: the cold-tier overlay '
        '(the per-batch host sync worth attributing)',
    'stitch':
        'mesh loaders: Batch pytree assembly',
    'recv':
        'host-runtime DistLoader: channel dequeue',
    'collate':
        'host-runtime DistLoader: message -> static-shape Batch '
        '(carries producer_trace/producer_span link fields)',
    'producer.sample':
        'sampling worker subprocess: one sample+send',
    'server.fetch':
        'DistServer: one blocking buffer pull for a client',
    'client.fetch':
        'DistClient: one RPC fetch round trip',
    'loader.sample':
        'NodeLoader._produce: one sample_from_nodes call (host '
        'dispatch of the sampler program)',
    'loader.collate':
        'NodeLoader._produce: SamplerOutput -> Batch (feature and '
        'label lookups, pytree assembly)',
    'feature.get':
        'loader.transform.to_data / to_hetero_data: the node-feature '
        'lookup inside collate (typed batches: one span per node '
        'type, field ntype)',
    'fused.seeds':
        'fused epoch drivers: host shuffle + stack (+ chunk padding) '
        'of the epoch\'s seed set and the epoch key\'s fold-in (an '
        'eager dispatch), before the first program dispatch',
    'fused.epoch':
        'fused epoch drivers: one whole run() call',
    'fused.dispatch':
        'fused epoch drivers: one chunk/program dispatch (tiered '
        "epochs tag phase='collect'|'train')",
    'feature.cold_overlay':
        'tiered fused epochs: the between-dispatch host cold service '
        'for one chunk (cache serve + host overlay + admissions; '
        'steps = batches corrected)',
    'fused.init_state':
        'FusedTreeEpoch.init_state: param init from the dummy batch',
    'dist.shard_build':
        'DistDataset.from_device_coo: the whole build of a dataset\'s '
        'shards on the mesh (partition book, relabel, exchange and '
        'sort of the COO, feature and label shards, the devices\' '
        'work included) — num_parts, edge_capacity (stated), '
        'exchange_capacity (the exchange width that follows from it) '
        'and, on the end event, nodes and edges per device',
    'exchange.layout':
        'mesh samplers, build time: one span per compiled SPMD step '
        'with the resolved exchange layout (dense/compact/hier/'
        'ragged), num_parts and slack',
    'exchange.stage':
        'parallel.exchange.capacity_spec, build time: hierarchical '
        'stage capacities (rows, cols, stage1_cap, stage2_cap) for '
        'one planned exchange',
    'serving.infer':
        'serving.frontend executor: one warm bucketed dispatch '
        '(device program + tiered host fill) — bucket, requests, '
        'seeds; queue wait is OUTSIDE this span (serving.request '
        'latency_ms minus this span = admission/coalescing wait)',
    'serving.route':
        'FleetRouter (request-trace root): one routed serve request '
        'submit→resolve, spanning the replica RPC + coalesced '
        'dispatch — replica, outcome; span_id == trace_id '
        '(recorded via telemetry.tracing, tail-retained)',
    'serving.rpc':
        'DistServer.serve_infer: one serve RPC on the server process '
        '(submit→future resolve) — the cross-process edge under '
        'serving.route (telemetry.tracing)',
    'serving.queue_wait':
        'serving frontend, per request: admission enqueue → '
        'coalesce pickup (the wait the coalescing executor imposed; '
        'also a live histogram under the same name)',
    'serving.dispatch_slice':
        'serving frontend, per request: this request\'s share of one '
        'coalesced dispatch (pickup → demux resolve) — bucket, '
        'requests riding the same dispatch (telemetry.tracing)',
    'serving.sample_collect':
        'serving engine, per dispatch: the neighbor-sampling collect '
        'program inside a tiered dispatch, parented under the '
        'dispatch slice — with serving.cold_fill it splits sampling '
        'cost from feature-fill cost (telemetry.tracing)',
    'serving.cold_fill':
        'serving engine, per dispatch: the tiered host cold-path '
        'feature fill inside the dispatch (cache serve + host '
        'gather), parented under the dispatch slice '
        '(telemetry.tracing)',
}


#: live-metric vocabulary (ISSUE 12): every counter/gauge/histogram
#: registered with the live ops registry (`telemetry.live`) must use a
#: ``snake.dot`` name from this table — enforced statically by the
#: glint ``metric-name`` pass, the metric twin of the event-schema
#: pass above.  The value is ``'<type>: <doc>'`` where type is one of
#: ``counter`` / ``gauge`` / ``histogram`` (the pass also checks the
#: registration call matches the declared type).  This table is the
#: ONE metrics vocabulary `gather_metrics` and the fleet `/metrics`
#: scrape share; an undeclared metric is a dashboard panel nobody can
#: discover.
METRIC_NAMES: Dict[str, str] = {
    'ops.scrapes_total':
        'counter: opsserver — HTTP requests answered by the ops '
        'endpoint (any of /metrics, /varz, /healthz)',
    'recorder.ring_dropped':
        'gauge: EventRecorder.stats()["ring_dropped"] — events lost '
        'to in-memory ring overflow (nonzero = the flight recorder '
        'is a sliding window, see the recorder.overflow event)',
    'serving.queue_depth':
        'gauge: AdmissionController.depth() at scrape time — '
        'requests waiting for the coalescing executor',
    'serving.in_flight':
        'gauge: requests inside the current coalesced dispatch '
        '(frontend executor state, read under its lock)',
    'serving.coalesce_fill_ratio':
        'gauge: seeds/bucket_capacity of the most recent coalesced '
        'dispatch — how much of the chosen bucket real traffic '
        'filled (low = padding-dominated dispatches)',
    'serving.requests_total':
        'counter: requests resolved OK by the serving executor',
    'serving.seeds_total':
        'counter: seeds served across all resolved requests',
    'serving.dispatches_total':
        'counter: coalesced device dispatches the executor ran',
    'serving.failed_total':
        'counter: requests resolved with an executor error '
        '(typed resolve — never a silent drop)',
    'serving.admitted_total':
        'counter: requests past admission into the bounded queue',
    'serving.shed_total':
        'counter: typed load-sheds, labeled by reason '
        '(queue_full|deadline|too_large|draining|shutdown)',
    'serving.shed_rate':
        'gauge: shed/(admitted+shed) over process lifetime — the '
        'overload signal the fleet scrape alarms on',
    'serving.request_latency':
        'histogram: end-to-end request latency (arrival→resolve, '
        'seconds; log2 buckets), labeled by serving bucket capacity',
    'serving.slo.p50_ms':
        'gauge: SloTracker short-window request latency p50 (ms)',
    'serving.slo.p99_ms':
        'gauge: SloTracker short-window request latency p99 (ms)',
    'serving.slo.qps':
        'gauge: SloTracker short-window completed-request rate',
    'serving.slo.qps_ratio':
        'gauge: short-window qps / GLT_SERVING_SLO_QPS (only '
        'exported when the target is configured)',
    'serving.slo.burn_rate':
        'gauge: latency-SLO error-budget burn rate per sliding '
        'window (violating_fraction / 1% budget vs '
        'GLT_SERVING_SLO_P99_MS; >1.0 = budget burning faster than '
        'allotted), labeled by window seconds',
    'cache.hits_total':
        'counter: cold-cache hits, labeled by scope '
        '(feature|dist|serving|hetero) — mirrors the cache.hit '
        'events (scope=hetero is pinned 0: no cache there yet, '
        'ROADMAP item 3 — visible live, not artifact-only)',
    'cache.misses_total':
        'counter: cold-cache misses (host-gather work), by scope',
    'cache.admits_total':
        'counter: rows admitted into the HBM victim ring, by scope',
    'cache.evicts_total':
        'counter: residents displaced by admissions, by scope',
    'cache.hit_rate':
        'gauge: hits/(hits+misses) summed across cache scopes',
    'cache.hbm_served_rate':
        'gauge: 1 - cold_misses/lookups from the dist feature '
        'counters — total fraction of feature lookups served from '
        'HBM (hot tier + victim cache)',
    'dist.feature.lookups':
        'counter: all mesh feature lookups (the hbm_served_rate '
        'denominator; ticked by ExchangeTelemetry drains)',
    'dist.feature.cold_lookups':
        'counter: lookups past the hot tier (the cache_hit_rate '
        'denominator)',
    'dist.feature.cold_misses':
        'counter: cold lookups the host gather served',
    'dist.feature.cache_hits':
        'counter: cold lookups the HBM victim cache served',
    'exchange.padding_waste_pct':
        'gauge: 100*(1 - sent/slots) over the frontier exchange '
        'counters — the live padding-waste number the scale '
        'envelope tracks offline',
    'fused.compile.hits':
        'counter: _counted_jit dispatches served by a warm '
        'in-memory executable',
    'fused.compile.misses':
        'counter: _counted_jit dispatches that added an executable '
        '(XLA compile or persistent-cache load; nonzero after '
        'warmup = a shape escaped bucketing)',
    'gns.bias_steps_total':
        'counter: compiled GNS-biased sampler steps built '
        '(node + link modes)',
    'gns.sketch_updates_total':
        'counter: cached-set bitmask refreshes (cache-ring version '
        'bumps reaching the sampling bias)',
    'rpc.retries':
        'counter: transport faults absorbed by the RPC resilience '
        'layer (one per rpc.retry event)',
    'rpc.replay_cache_entries':
        'gauge: live entries across the RPC server replay cache '
        '(exactly-once occupancy; near the eviction caps = retries '
        'at risk of ReplayEvictedError)',
    'producer.restarts_total':
        'counter: sampling-worker restarts by the producer '
        'supervisor',
    'snapshot.saves_total':
        'counter: durable snapshot publishes (SnapshotManager.save '
        'ok=True)',
    'snapshot.save_failures_total':
        'counter: absorbed snapshot write failures (ok=False)',
    'snapshot.save_age_seconds':
        'gauge: seconds since the last successful snapshot save '
        '(absent until one lands; growing past the cadence = '
        'durability stalled)',
    'snapshot.restore_age_seconds':
        'gauge: seconds since the last snapshot restore (absent '
        'unless this process resumed/rolled back)',
    'postmortem.dumps_total':
        'counter: post-mortem bundles written to GLT_POSTMORTEM_DIR',
    'fleet.replicas':
        'gauge: FleetRouter replica count by state, labeled '
        'state=healthy|overloaded|draining|quarantined|dead '
        '(scrape-time evaluation off the replica table)',
    'fleet.redrives_total':
        'counter: in-flight requests redriven from a lost replica '
        'onto a survivor (each redriven at most once — the '
        'exactly-once failover ledger)',
    'fleet.evictions_total':
        'counter: replicas evicted from rotation after consecutive '
        'heartbeat misses (flapped replicas that return are '
        're-admitted and counted again on a later eviction)',
    'fleet.quarantines_total':
        'counter: replicas quarantined by the flap damper (≥3 '
        'dead→healthy readmits inside GLT_FLEET_FLAP_WINDOW_S) — '
        're-admission waits out an exponential backoff, doubling '
        'per quarantine of the same replica',
    'scale.replicas':
        'counter: ElasticController scaling actions executed, '
        'labeled dir=out|in (each tick = one replica admitted to / '
        'retired from rotation; rolled-back decisions do not tick)',
    'serving.swaps_total':
        'counter: hot model-swap attempts, labeled '
        'outcome=ok|rolled_back|aborted (rolled_back = '
        'offline_reference parity check refused the new version; '
        'aborted = executor never quiesced, probe never ran)',
    'aot.cache_hits_total':
        'counter: bucket executables restored from the persistent '
        'AOT cache (GLT_AOT_CACHE_DIR) instead of recompiling',
    'aot.cache_misses_total':
        'counter: bucket warmups that paid an XLA compile (absent/'
        'stale/corrupt cache entries all land here)',
    'ingest.events_total':
        'counter: edge-insert events applied to the delta-CSR by '
        'this process (WAL replays after a restart included — they '
        'are real applies this process performed)',
    'ingest.lag_events':
        'gauge: WAL events appended but not yet applied (the '
        'freshness debt; past GLT_INGEST_MAX_LAG the ingestion '
        'healthz component flips unhealthy)',
    'ingest.compactions_total':
        'counter: durable base compactions (snapshot published + '
        'WAL reset to the surviving suffix)',
    'graph.version':
        'gauge: the streaming graph\'s current published version — '
        'every reader dispatch pins exactly one of these; the value '
        'moving is ingest reaching the data plane',
    'partition.adoptions_total':
        'counter: partition-ownership transfers executed '
        '(failover.adopt_shard: durable shard loaded, book version '
        'bumped, survivor serving the orphaned range)',
    'partition.book_version':
        'gauge: the PartitionBook\'s current published version (0 = '
        'identity ownership; each adoption bumps it and every '
        'reader re-fences at its next dispatch seam)',
    'partition.recovery_secs':
        'gauge: classification→first-served-batch wall time of the '
        'most recent partition adoption (shard load + lane rebuild '
        '+ exchange-plan recompile)',
    'timeseries.samples_total':
        'counter: cadence-sampler sweeps completed by the '
        'TimeSeriesStore (one per GLT_TS_CADENCE_MS tick; a stalled '
        'counter here means the history rings have stopped filling)',
    'timeseries.series':
        'gauge: ring-buffered series currently held by the '
        'TimeSeriesStore (gauges plus counters-as-rates)',
    'fleet.scrapes_total':
        'counter: FleetScraper sweeps over the replica target set '
        '(one per GLT_FLEET_SCRAPE_MS tick or explicit scrape)',
    'fleet.scrape_errors_total':
        'counter: replica scrapes that failed (unreachable '
        'endpoint, malformed exposition), labeled by replica',
    'fleet.replicas_up':
        'gauge: replicas whose most recent scrape succeeded and '
        'whose /healthz rollup reported ok — the federation\'s own '
        'liveness view of the fleet',
    'gns.range_hotness':
        'gauge: decayed visit mass of one PartitionBook range from '
        'the GNS DecayedSketch top-K export, labeled by partition '
        '(only the K hottest ranges are exported)',
    'exchange.local_ids_total':
        'counter: exchange ids (frontier + feature) whose '
        'destination range was the requesting device\'s own — the '
        'attribution matrix diagonal, ticked at attribution drains',
    'exchange.cross_ids_total':
        'counter: exchange ids routed to a NON-self partition range '
        '(off-diagonal attribution mass — what locality-aware '
        'partitioning exists to shrink)',
    'partition.replicated_rows':
        'gauge: per-device rows of the read-only remote-row replica '
        'cache (`dist_data.build_replica_cache`) — the hot-row '
        'budget the masked gather serves locally instead of '
        'exchanging (0 = replication off)',
    'locality.edge_cut_frac':
        'gauge: fraction of edges crossing partitions under the '
        'most recent locality_partition run — the streaming '
        'partitioner\'s objective, measured on its own output',
    'serving.queue_wait':
        'histogram: per-request admission enqueue → coalesce pickup '
        'wait (seconds; log2 buckets) — overload diagnosis without '
        'inferring waits from shed diagnostics',
    'serving.traces_retained_total':
        'counter: request traces kept by the tail-retention verdict '
        '(slow/failed/sampled — telemetry.tracing; the /traces ring '
        'is bounded, this counts total captures)',
    'memory.tier_bytes':
        'gauge: bytes currently held by one memory tier, labeled '
        'tier=hot|cold_cache|streaming|gns|aot|wal|pinned_host '
        '(scrape-time callback from each owner — '
        'telemetry.memaccount)',
    'memory.tier_peak_bytes':
        'gauge: high-watermark of memory.tier_bytes since the '
        'owner registered (tracked at scrape time, by tier)',
    'fleet.headroom_qps':
        'gauge: sustainable request rate minus carried short-window '
        'QPS for this replica (traffic-weighted per-bucket EWMA '
        'serve-cost model — telemetry.memaccount.CapacityModel; '
        'the admission signal for SLO-driven autoscaling)',
}


#: closed label-key vocabulary of the live metric plane.  Every
#: ``labels={...}`` at a counter/gauge/histogram registration site
#: must draw its KEYS from this table (enforced statically by the
#: glint ``metric-label-cardinality`` pass) and each entry documents
#: the closed/bounded VALUE set — the property that keeps scrape
#: cardinality enumerable (a label whose values are unbounded is a
#: time-series leak: every new value mints a family member forever).
METRIC_LABELS: Dict[str, str] = {
    'scope':
        'cold-cache scope: feature|dist|serving|hetero (the four '
        'cache flavors — see cache.*_total)',
    'bucket':
        'serving bucket capacity: one of the GLT_SERVING_BUCKETS '
        'ladder seeds (default 1,2,4,8,16 — bounded by the ladder '
        'length)',
    'state':
        'FleetRouter replica state: healthy|overloaded|draining|'
        'quarantined|dead (fixed five-state machine)',
    'dir':
        'ElasticController scale direction: out|in (the two-way '
        'vocabulary of scale.replicas)',
    'reason':
        'admission shed reason: queue_full|deadline|too_large|'
        'draining|shutdown (the typed rejection vocabulary)',
    'outcome':
        'hot-swap outcome: ok|rolled_back|aborted (fixed three-way '
        'verdict of serving.swaps_total)',
    'window':
        'SLO sliding window: one of SloTracker.windows rendered as '
        '"<seconds>s" (default 60s|300s — bounded by the '
        'configured window tuple)',
    'replica':
        'fleet replica name: bounded by the fleet size (the '
        'FleetScraper target set / FleetRouter replica table)',
    'partition':
        'partition/range index: 0..P-1, bounded by the mesh '
        'num_parts (PartitionBook range ids)',
    'tier':
        'memory accounting tier: hot|cold_cache|streaming|gns|aot|'
        'wal|pinned_host (the closed memaccount.TIERS vocabulary — '
        'seven fixed byte-gauge families, never per-object)',
}


def registered(kind: str) -> bool:
  return kind in EVENT_KINDS
